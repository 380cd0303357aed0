"""Plain reference of the MiMo-V2 block (`model_type` `mimo_v2`,
huggingface.co/XiaomiMiMo/MiMo-V2.5 `config.json`), as one chip of an
expert-parallel deployment holds it: pre-norm RMSNorm blocks; fused
q/k/v with more query than key/value heads and a smaller value head;
rotary positions on the leading `rotary_dim` dimensions with a theta
per layer kind; full causal layers beside sliding-window layers whose
softmax has one learned sink logit a head; values scaled; a SwiGLU
dense layer first, then routed layers: sigmoid router over all
experts, top-k chosen by score + bias, weighted by the unbiased score,
normalised; an untied head. float32 `jax.numpy`, matmuls at "highest"
precision, no cache, no kernels, no sorting of assignments: every held
expert runs over every token and is weighted 0 where it was not
chosen. It shares no code with the program: it is given the program's
weights by name (`Model.get_states()`), the architecture's numbers and
the range of experts held, and nothing else.

`held = [first, count]`: only those experts' weights exist here. What
the other experts would have added is left out, exactly as in the
program, and that partial result is what goes on to the next layer.

A weight is cast to float32 where it is used, layer by layer and
expert by expert, and attention runs one key/value head's group of
query heads at a time (`lax.map`), so the check at the published
widths fits beside the model.

Departures from the published description are listed under `assumed`
in configs/mimo-v2.5.json: rotate-half pairing, `attention_value_scale`
multiplying v, a window of `window` keys including the current one,
`attention_chunk_size` read as that window.
"""
import functools
import math

import jax
import jax.numpy as jnp

PREFIX = "HybridWindowMoELM"


def _arch(kw):
    """The keyword arguments as one hashable, static value."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in kw.items()))


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta, rotary_dim):
    """x [B, S, H, D] at positions 0..S-1; rotate-half over the first
    `rotary_dim` dimensions."""
    S = x.shape[1]
    half = rotary_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / rotary_dim)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def forward(states, ids, arch, lower=None):
    """[B, S] token ids -> [B, S, vocab held] logits, layer by layer.
    `lower` names a dtype below the configuration's for the
    lower-precision control (`lower_precision_choice`): every
    matrix but the router's and every matrix product's input are
    rounded to it; sums stay float32."""
    a = dict(arch)
    eps = a["norm_eps"]
    Hq, Dk, Dv = a["num_heads"], a["head_dim"], a["v_head_dim"]
    first, count = a["held"]

    def low(t):
        return t if lower is None else t.astype(lower).astype(jnp.float32)

    def w(name, rounded=True):
        t = jnp.asarray(states[f"{PREFIX}.{name}"], jnp.float32)
        return low(t) if rounded and t.ndim >= 2 else t

    B, S = ids.shape
    h = w("embed.W")[ids]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    for li, kind in enumerate(a["layer_pattern"]):
        pre = f"blocks.l{li}"
        window = kind == 1
        Hkv = a["kv_heads_window"] if window else a["kv_heads_full"]
        theta = a["rope_theta_window"] if window else a["rope_theta_full"]
        G = Hq // Hkv
        x = low(_rms(h, w(f"{pre}.ln1.gamma"), eps))
        qkv = x @ w(f"{pre}.attn.W_qkv")
        q = qkv[..., :Hq * Dk].reshape(B, S, Hq, Dk)
        k = qkv[..., Hq * Dk:Hq * Dk + Hkv * Dk].reshape(B, S, Hkv, Dk)
        v = qkv[..., Hq * Dk + Hkv * Dk:].reshape(B, S, Hkv, Dv)
        v = v * a["value_scale"]
        q = low(_rope(q, theta, a["rotary_dim"]))
        k = low(_rope(k, theta, a["rotary_dim"]))
        v = low(v)
        allowed = j <= i
        if window:
            allowed = allowed & (j > i - a["window"])
            sink = w(f"{pre}.attn.sink").reshape(Hkv, G)
        else:
            sink = jnp.zeros((Hkv, G), jnp.float32)     # not used

        def group(args, window=window, allowed=allowed):
            qg, kg, vg, sg = args     # [B,S,G,Dk] [B,S,Dk] [B,S,Dv] [G]
            s = jnp.einsum("bqgd,bkd->bgqk", qg, kg) / math.sqrt(Dk)
            s = jnp.where(allowed, s, -jnp.inf)
            if window:
                col = jnp.broadcast_to(sg[None, :, None, None],
                                       s.shape[:-1] + (1,))
                p = jax.nn.softmax(jnp.concatenate([s, col], -1), -1)
                p = p[..., :-1]                 # the sink's weight is dropped
            else:
                p = jax.nn.softmax(s, -1)
            return jnp.einsum("bgqk,bkd->bqgd", low(p), vg)

        att = jax.lax.map(group, (
            q.reshape(B, S, Hkv, G, Dk).transpose(2, 0, 1, 3, 4),
            k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3), sink))
        att = low(att.transpose(1, 2, 0, 3, 4).reshape(B, S, Hq * Dv))
        h = h + att @ w(f"{pre}.attn.W_o")
        x = low(_rms(h, w(f"{pre}.ln2.gamma"), eps))
        if a["moe_layers"][li]:
            # the router is float32 at every precision of the rest
            sig = jax.nn.sigmoid(x @ w(f"{pre}.moe.W_r", rounded=False))
            _, idx = jax.lax.top_k(sig + w(f"{pre}.moe.b"),
                                   a["experts_per_token"])
            chosen = jnp.take_along_axis(sig, idx, -1)
            share = chosen / chosen.sum(-1, keepdims=True)
            for e in range(count):
                we = jnp.where(idx == first + e, share, 0.0).sum(-1)
                g = x @ w(f"{pre}.moe.W_g")[e]
                u = x @ w(f"{pre}.moe.W_u")[e]
                h = h + we[..., None] * (
                    low(jax.nn.silu(g) * u) @ w(f"{pre}.moe.W_d")[e])
        else:
            g = x @ w(f"{pre}.mlp.W_g")
            u = x @ w(f"{pre}.mlp.W_u")
            h = h + low(jax.nn.silu(g) * u) @ w(f"{pre}.mlp.W_d")
    return low(_rms(h, w("ln_f.gamma"), eps)) @ w("head.W")


@functools.partial(jax.jit, static_argnames=("arch", "lower"))
def _logits(states, ids, arch, lower=None):
    with jax.default_matmul_precision("highest"):
        return forward(states, ids, arch, lower)


def logits(states, ids, **arch):
    """[B, S, vocab held] next-token logits, float32."""
    return _logits(states, jnp.asarray(ids), _arch(arch))


@functools.partial(jax.jit, static_argnames="arch")
def _shortfall(states, ids, tokens, arch):
    lg = _logits(states, ids, arch)[:, :-1]
    got = jnp.take_along_axis(lg, tokens[..., None], -1)[..., 0]
    return lg.max(-1) - got, jnp.std(lg)


def served_shortfall(states, seqs, tokens=None, **arch):
    """For each sequence (prompt + served reply, right-padded to one
    length) and each next token, how far the reference's logit of that
    token lies under the reference's own best logit at that position:
    (shortfall [B, S-1], std of the logits). Row b column t judges
    token seqs[b, t+1], or `tokens[b, t]` where another chooser's
    tokens are judged along the same sequences (the control below);
    the caller masks the served positions. Causal, so the padding
    changes nothing before it. Computed on the device; only [B, S]
    floats come back."""
    seqs = jnp.asarray(seqs)
    tokens = seqs[:, 1:] if tokens is None else jnp.asarray(tokens)
    return _shortfall(states, seqs, tokens, _arch(arch))


@functools.partial(jax.jit, static_argnames=("arch", "lower"))
def _lower_choice(states, ids, arch, lower):
    return _logits(states, ids, arch, lower)[:, :-1].argmax(-1)


def lower_precision_choice(states, seqs, lower, **arch):
    """[B, S-1] greedy next tokens along `seqs` of this reference with
    every matrix but the router's and every matrix product's input
    rounded to `lower`: what a tier computed one precision below the
    configuration's would serve. `reference/mimo_v2_control.py` has
    `served_shortfall` judge them as it judges the served tokens."""
    return _lower_choice(states, jnp.asarray(seqs), _arch(arch), lower)
