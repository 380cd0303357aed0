"""Plain reference of ResNet (He et al., arXiv:1512.03385, Table 1;
bottleneck blocks with the stride on the 3x3, projection shortcuts
where shape changes, as the reference's `examples/cnn` builds it), in
inference mode: BatchNorm uses its running statistics. float32
`lax.conv_general_dilated` at "highest" precision. It shares no code
with the program: it is given the program's arrays by name
(`Model.get_states()`) and nothing else.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
EPS = 1e-5


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


@functools.partial(jax.jit, static_argnames=("depth", "prefix"))
def logits(states, images, depth=50, prefix="ResNet"):
    """[B, 3, H, W] float32 -> [B, classes] logits."""
    def g(name):
        return jnp.asarray(states[f"{prefix}.{name}"], jnp.float32)

    def bn(x, name):
        inv = g(name + ".scale") / jnp.sqrt(g(name + ".running_var") + EPS)
        shift = g(name + ".bias") - g(name + ".running_mean") * inv
        return x * inv[None, :, None, None] + shift[None, :, None, None]

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(images, jnp.float32)
        x = jax.nn.relu(bn(_conv(x, g("conv1.W"), 2, 3), "bn1"))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              ((0, 0), (0, 0), (1, 1), (1, 1)))
        for stage, blocks in enumerate(BLOCKS[depth]):
            for b in range(blocks):
                p = f"layer{stage + 1}.l{b}"
                stride = 2 if (b == 0 and stage > 0) else 1
                y = jax.nn.relu(bn(_conv(x, g(p + ".conv1.W"), 1, 0),
                                   p + ".bn1"))
                y = jax.nn.relu(bn(_conv(y, g(p + ".conv2.W"), stride, 1),
                                   p + ".bn2"))
                y = bn(_conv(y, g(p + ".conv3.W"), 1, 0), p + ".bn3")
                if b == 0:
                    x = bn(_conv(x, g(p + ".downsample.conv.W"), stride, 0),
                           p + ".downsample.bn")
                x = jax.nn.relu(y + x)
        x = x.mean((2, 3))
        return x @ g("fc.W") + g("fc.b")
