"""Plain reference of the LFM2-MoE block (`model_type` `lfm2_moe`,
huggingface.co/LiquidAI/LFM2-24B-A2B `config.json`): pre-norm RMSNorm
blocks whose operator is, by `layer_types`, a gated short convolution
(input projection into thirds b, c, x; u = b * x; a depthwise causal
convolution of `conv_L` taps over u; gated by c; output projection) or
grouped-query attention (RMSNorm over each query and key head, then
rotary positions over the whole head, causal softmax); a SwiGLU dense
MLP in the first `num_dense_layers` layers, then routed layers: sigmoid
router over all experts, top-k chosen by score + bias, weighted by the
unbiased score over (their sum + `router_sum_eps`); the published
`routed_scaling_factor` is 1 and is not multiplied in; the head is
the embedding again. float32 `jax.numpy`,
matmuls at "highest" precision, no cache, no kernels, no sorting of
assignments: the convolution is an explicit sum over `conv_L` shifted
copies of u, and every held expert runs over every token and is
weighted 0 where it was not chosen. It shares no code with the
program: it is given the program's weights by name
(`Model.get_states()`), the architecture's numbers and the range of
experts held, and nothing else.

`held = [first, count]`: only those experts' weights exist here (all
64 in the served configuration). A weight is cast to float32 where it
is used, layer by layer and expert by expert (`lax.scan` over the held
experts), and attention runs one key/value head's group of query heads
at a time (`lax.map`), so the check at the published widths fits
beside the model.

Departures from the published description are listed under `assumed`
in configs/lfm2-24b-a2b.json: the tied head, rotate-half pairing, the
order b, c, x of the input projection's thirds.
"""
import functools
import math

import jax
import jax.numpy as jnp

PREFIX = "ShortConvMoELM"


def _arch(kw):
    """The keyword arguments as one hashable, static value."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in kw.items()))


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1; rotate-half over all D."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _normed_rotary(x, gain, theta, eps):
    """A query's or key's heads: the norm over each head FIRST, then
    the rotation."""
    return _rope(_rms(x, gain, eps), theta)


def _short_conv(u, taps):
    """z_t = sum_j taps[j] * u_{t-(L-1)+j} for u [B, S, d] and taps
    [L, d]: L copies of u, copy j shifted L-1-j positions later with
    zeros before position 0."""
    S, L = u.shape[1], taps.shape[0]
    z = 0.0
    for j in range(L):
        shift = L - 1 - j
        z = z + taps[j] * jnp.pad(u, ((0, 0), (shift, 0), (0, 0)))[:, :S]
    return z


def _route(x, W_r, bias, k, sum_eps, idx=None):
    """(chosen experts [..., k], their shares [..., k]): chosen by
    score + bias (or given as `idx`: another computation's choice),
    weighted by the score alone."""
    sig = jax.nn.sigmoid(x @ W_r)
    if idx is None:
        _, idx = jax.lax.top_k(sig + bias, k)
    chosen = jnp.take_along_axis(sig, idx, -1)
    return idx, chosen / (chosen.sum(-1, keepdims=True) + sum_eps)


def forward(states, ids, arch, lower=None, routes=None, taken=None):
    """[B, S] token ids -> [B, S, vocab] logits, layer by layer.
    `lower` names a dtype below the configuration's for the
    lower-precision control (`lower_precision_choice`): every matrix
    but the router's and every matrix product's input are rounded to
    it; sums stay float32. For `routing_witness`: each routed layer's
    chosen experts [B, S, k] are appended to the list `taken`, and
    taken from `routes` (one entry a routed layer) where it is given."""
    a = dict(arch)
    eps = a["norm_eps"]
    Hq, Hkv, D = a["num_heads"], a["kv_heads"], a["head_dim"]
    G = Hq // Hkv
    first, count = a["held"]

    def low(t):
        return t if lower is None else t.astype(lower).astype(jnp.float32)

    def f32(t, rounded=True):
        t = jnp.asarray(t, jnp.float32)
        return low(t) if rounded and t.ndim >= 2 else t

    def w(name, rounded=True):
        return f32(states[f"{PREFIX}.{name}"], rounded)

    B, S = ids.shape
    h = w("embed.W")[ids]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    for li, kind in enumerate(a["layer_types"]):
        pre = f"blocks.l{li}"
        x = low(_rms(h, w(f"{pre}.ln1.gamma"), eps))
        if kind == "conv":
            b, c, x = jnp.split(x @ w(f"{pre}.conv.W_in"), 3, -1)
            z = _short_conv(low(b * x), w(f"{pre}.conv.w"))
            h = h + low(c * z) @ w(f"{pre}.conv.W_out")
        else:
            qkv = x @ w(f"{pre}.attn.W_qkv")
            q = qkv[..., :Hq * D].reshape(B, S, Hq, D)
            k = qkv[..., Hq * D:(Hq + Hkv) * D].reshape(B, S, Hkv, D)
            v = low(qkv[..., (Hq + Hkv) * D:].reshape(B, S, Hkv, D))
            q = low(_normed_rotary(q, w(f"{pre}.attn.q_norm"),
                                   a["rope_theta"], eps))
            k = low(_normed_rotary(k, w(f"{pre}.attn.k_norm"),
                                   a["rope_theta"], eps))

            def group(args):
                qg, kg, vg = args          # [B,S,G,D] [B,S,D] [B,S,D]
                s = jnp.einsum("bqgd,bkd->bgqk", qg, kg) / math.sqrt(D)
                p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), -1)
                return jnp.einsum("bgqk,bkd->bqgd", low(p), vg)

            att = jax.lax.map(group, (
                q.reshape(B, S, Hkv, G, D).transpose(2, 0, 1, 3, 4),
                k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
            att = low(att.transpose(1, 2, 0, 3, 4).reshape(B, S, Hq * D))
            h = h + att @ w(f"{pre}.attn.W_o")
        x = low(_rms(h, w(f"{pre}.ln2.gamma"), eps))
        if li >= a["num_dense_layers"]:
            # the router is float32 at every precision of the rest
            idx, share = _route(
                x, w(f"{pre}.moe.W_r", rounded=False), w(f"{pre}.moe.b"),
                a["experts_per_token"], a["router_sum_eps"],
                None if routes is None else routes[li - a["num_dense_layers"]])
            if taken is not None:
                taken.append(idx)

            def expert(h, ew, x=x, idx=idx, share=share):
                e, W_g, W_u, W_d = ew      # as stored: cast here, one expert
                we = jnp.where(idx == first + e, share, 0.0).sum(-1)
                act = low(jax.nn.silu(x @ f32(W_g)) * (x @ f32(W_u)))
                return h + we[..., None] * (act @ f32(W_d)), None

            h, _ = jax.lax.scan(expert, h, (
                jnp.arange(count), *(states[f"{PREFIX}.{pre}.moe.{n}"]
                                     for n in ("W_g", "W_u", "W_d"))))
        else:
            g = x @ w(f"{pre}.mlp.W_g")
            u = x @ w(f"{pre}.mlp.W_u")
            h = h + low(jax.nn.silu(g) * u) @ w(f"{pre}.mlp.W_d")
    return low(_rms(h, w("ln_f.gamma"), eps)) @ w("embed.W").T


@functools.partial(jax.jit, static_argnames=("arch", "lower"))
def _logits(states, ids, arch, lower=None):
    with jax.default_matmul_precision("highest"):
        return forward(states, ids, arch, lower)


def logits(states, ids, **arch):
    """[B, S, vocab] next-token logits, float32."""
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
    tokens are judged along the same sequences (the control). Causal
    (attention by its mask, the convolution by its shifts), so the
    padding changes nothing before it. Computed on the device; only
    [B, S] floats come back."""
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
    configuration's would serve. `reference/lfm2_moe_control.py` has
    `served_shortfall` judge them as it judges the served tokens."""
    return _lower_choice(states, jnp.asarray(seqs), _arch(arch), lower)


@functools.partial(jax.jit, static_argnames=("arch", "lower"))
def _routed_choice(states, ids, arch, lower, routes):
    taken = []
    with jax.default_matmul_precision("highest"):
        lg = forward(states, ids, arch, lower, routes, taken)
    return lg[:, :-1].argmax(-1), jnp.stack(taken)


def routing_witness(states, seqs, lower, **arch):
    """What rounding to `lower` does along `seqs`, and how much of it
    through the router's choice: (the float32 reference's chosen
    experts [layers, B, S, k]; the chosen experts of the reference
    rounded to `lower`; its greedy next tokens [B, S-1]; its greedy
    next tokens where every routed layer is GIVEN the float32
    reference's chosen experts). `served_shortfall` judges both sets of
    tokens as it judges the served ones."""
    arch, seqs = _arch(arch), jnp.asarray(seqs)
    _, exact = _routed_choice(states, seqs, arch, None, None)
    free, rounded = _routed_choice(states, seqs, arch, lower, None)
    forced, _ = _routed_choice(states, seqs, arch, lower, tuple(exact))
    return exact, rounded, free, forced
