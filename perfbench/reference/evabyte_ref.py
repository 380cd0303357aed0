"""Plain reference of the EvaByte block (`model_type` `evabyte`,
`attention_class` `eva`; huggingface.co/EvaByte/EvaByte `config.json`
and the modelling code beside it; Zheng et al., "Efficient Attention
via Control Variates", arXiv:2302.04542): pre-norm RMSNorm blocks with
gain 1 + g, rotary positions over the whole head, a SwiGLU MLP, and an
attention that keeps exact keys and values only inside the query's own
block of `window` positions and sees everything before that block as
one learned-pooled key and value a chunk of `chunk` positions, in one
softmax over both. W = `window`, C = `chunk`:

    chunk c = positions cC .. cC+C-1
        a_j  = softmax_{j in c}(k_j . phi)
        sk_c = sum_j a_j k_j + mu,   sv_c = sum_j a_j v_j
    query i, w = i // W:
        local   { j : j // W = w, j <= i }
        remote  { c : (cC) // W < w }
        o_i = softmax over [q_i.k_j, q_i.sk_c] / sqrt(D), weighting [v_j, sv_c]

float32 `jax.numpy`, matmuls at "highest" precision, no cache, no
kernels: the summaries of ALL chunks are computed from the full
sequence, then each query's two sets are taken by the index rules
above, as explicit masks over its block's keys and over every summary.
It shares no code with the program: it is given the program's weights
by name (`Model.get_states()`), the architecture's numbers, and
nothing else.

So that 14,336 positions at the published widths fit beside the served
model (12.1 GB of a 16 GB chip), the check runs each layer's two
halves as programs of their own (`forward(..., jitted=True)`),
attention one block of `window` queries and `HEADS_AT_ONCE` heads at a
time, every product `window` rows at a time, and a weight is cast to
float32 where it is used; positions are an argument, so that no
rotation table is folded into a program as a constant.

Departures from the published description are listed under `assumed`
in configs/evabyte.json: no scale on k . phi, mu added to the pooled
key alone, head 0 of the `pred_heads` as the next byte, rotate-half
pairing.
"""
import math

import jax
import jax.numpy as jnp

PREFIX = "ChunkedAttnLM"
HEADS_AT_ONCE = 4


def _arch(kw):
    """The keyword arguments as one hashable, static value."""
    return tuple(sorted(kw.items()))


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, pos, theta):
    """x [B, S, H, D] at positions pos [S]; rotate-half over all D."""
    D = x.shape[-1]
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / D)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _summaries(k, v, phi, mu, C):
    """(sk, sv) [B, S // C, H, D] of every chunk complete in k, v
    [B, S, H, D]."""
    B, S, H, D = k.shape
    n = S // C
    kc = k[:, :n * C].reshape(B, n, C, H, D)
    vc = v[:, :n * C].reshape(B, n, C, H, D)
    a = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, phi), 2)
    return (jnp.einsum("bnch,bnchd->bnhd", a, kc) + mu,
            jnp.einsum("bnch,bnchd->bnhd", a, vc))


def _remote_mask(i, n, W, C):
    """[len(i), n]: which of n summaries the queries at positions i
    see: chunk c iff its first position lies in a block before the
    query's own."""
    return (jnp.arange(n)[None, :] * C) // W < (i // W)[:, None]


def _attention(q, k, v, sk, sv, pos, W, C, low):
    """q, k, v [B, S, H, D] at positions pos [S]; sk, sv [B, n, H, D]
    -> [B, S, H, D]: a block of W queries at a time, explicit masks."""
    B, S, H, D = q.shape
    n = sk.shape[1]
    hg = HEADS_AT_ONCE if H % HEADS_AT_ONCE == 0 else H
    out = []
    for lo in range(0, S, W):
        hi = min(lo + W, S)
        i = pos[lo:hi]
        local = i[None, :] <= i[:, None]                    # [q, k]
        mask = jnp.concatenate([local, _remote_mask(i, n, W, C)], -1)

        def heads(args, lo=lo, hi=hi, mask=mask):
            qg, kg, vg, skg, svg = args       # [B, ., hg, D]
            s = jnp.concatenate([
                jnp.einsum("bqhd,bkhd->bhqk", qg, kg),
                jnp.einsum("bqhd,bnhd->bhqn", qg, skg)], -1) / math.sqrt(D)
            p = low(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1))
            return (jnp.einsum("bhqk,bkhd->bqhd", p[..., :hi - lo], vg)
                    + jnp.einsum("bhqn,bnhd->bqhd", p[..., hi - lo:], svg))

        def by_group(t):          # [B, ., H, D] -> [H/hg, B, ., hg, D]
            return t.reshape(*t.shape[:2], H // hg, hg, D).transpose(
                2, 0, 1, 3, 4)

        o = jax.lax.map(heads, tuple(by_group(t) for t in (
            q[:, lo:hi], k[:, lo:hi], v[:, lo:hi], sk, sv)))
        out.append(o.transpose(1, 2, 0, 3, 4).reshape(B, hi - lo, H, D))
    return jnp.concatenate(out, 1)


def _rows(fn, x, at_once):
    """fn over x [B, S, .], `at_once` rows of S at a time, one piece
    after another (`lax.map`) where S is a whole number of pieces."""
    B, S, d = x.shape
    if S <= at_once or S % at_once:
        return fn(x)
    y = jax.lax.map(fn, x.reshape(B, S // at_once, at_once, d).swapaxes(0, 1))
    return y.swapaxes(0, 1).reshape(B, S, -1)


def _low(lower):
    return (lambda t: t) if lower is None else (
        lambda t: t.astype(lower).astype(jnp.float32))


def _matrix(t, low):
    """A stored matrix in float32, rounded for the control."""
    return low(jnp.asarray(t, jnp.float32))


def attention_layer(h, pos, ws, arch, lower=None):
    """h += Attn(RMSNorm(h)) for h [B, S, d] at positions pos [S]; ws =
    (g, W_qkv, phi, mu, W_o) as stored. phi, mu and g are float32 at
    every precision of the rest."""
    a, low = dict(arch), _low(lower)
    H, D, W, C = a["num_heads"], a["head_dim"], a["window"], a["chunk"]
    g, W_qkv, phi, mu, W_o = ws
    B, S, _ = h.shape
    with jax.default_matmul_precision("highest"):
        x = low(_rms(h, g, a["norm_eps"]))
        q, k, v = (_rows(lambda r, Wp=Wp: r @ _matrix(Wp, low), x, W
                         ).reshape(B, S, H, D)
                   for Wp in jnp.split(W_qkv, 3, -1))
        q = low(_rope(q, pos, a["rope_theta"]))
        k = low(_rope(k, pos, a["rope_theta"]))
        v = low(v)
        sk, sv = _summaries(k, v, phi, mu, C)
        att = low(_attention(q, k, v, low(sk), low(sv), pos, W, C, low))
        return h + _rows(lambda r: r @ _matrix(W_o, low),
                         att.reshape(B, S, H * D), W)


def mlp_layer(h, ws, arch, lower=None):
    """h += (silu(n W_g) * n W_u) W_d, n = RMSNorm(h); ws = (g, W_g,
    W_u, W_d) as stored."""
    a, low = dict(arch), _low(lower)
    g, W_g, W_u, W_d = ws
    with jax.default_matmul_precision("highest"):
        x = low(_rms(h, g, a["norm_eps"]))
        return h + _rows(
            lambda r: low(jax.nn.silu(r @ _matrix(W_g, low))
                          * (r @ _matrix(W_u, low))) @ _matrix(W_d, low),
            x, a["window"])


def head_layer(h, ws, arch, lower=None):
    """Next-byte logits: head 0's columns of RMSNorm(h) W_head."""
    a, low = dict(arch), _low(lower)
    g, W_head = ws
    with jax.default_matmul_precision("highest"):
        return low(_rms(h, g, a["norm_eps"])) @ _matrix(
            W_head, low)[:, :a["vocab_size"]]


_JITTED = {fn: jax.jit(fn, static_argnames=("arch", "lower"))
           for fn in (attention_layer, mlp_layer, head_layer)}


def forward(states, ids, arch, lower=None, jitted=False):
    """[B, S] token ids -> [B, S, vocab] logits of the next byte (head
    0's columns), layer by layer. `lower` names a dtype below the
    configuration's for the lower-precision control
    (`lower_precision_choice`): every matrix and every matrix product's
    input are rounded to it; sums stay float32. `jitted`: each layer's
    two halves as programs of their own, so that of 14,336 positions at
    the published widths only one half-layer's temporaries exist at a
    time (one program over all layers held 5.0 GB of them beside the
    served model: compiled for a described v5e, PR 35)."""
    a = dict(arch)
    run = (lambda fn, *args: _JITTED[fn](*args, arch=arch, lower=lower)) \
        if jitted else (lambda fn, *args: fn(*args, arch, lower))

    def w(*names):
        return tuple(states[f"{PREFIX}.{name}"] for name in names)

    pos = jnp.arange(ids.shape[1])
    h = _matrix(w("embed.W")[0], _low(lower))[ids]
    for li in range(a["num_layers"]):
        pre = f"blocks.l{li}"
        h = run(attention_layer, h, pos, w(
            f"{pre}.ln1.g", f"{pre}.attn.W_qkv", f"{pre}.attn.phi",
            f"{pre}.attn.mu", f"{pre}.attn.W_o"))
        h = run(mlp_layer, h, w(f"{pre}.ln2.g", f"{pre}.mlp.W_g",
                                f"{pre}.mlp.W_u", f"{pre}.mlp.W_d"))
    return run(head_layer, h, w("ln_f.g", "head.W"))


def logits(states, ids, lower=None, **arch):
    """[B, S, vocab] next-byte logits, float32."""
    return forward(states, jnp.asarray(ids), _arch(arch), lower, jitted=True)


@jax.jit
def _shortfall(lg, tokens):
    lg = lg[:, :-1]
    got = jnp.take_along_axis(lg, tokens[..., None], -1)[..., 0]
    return lg.max(-1) - got, jnp.std(lg)


def served_shortfall(states, seqs, tokens=None, **arch):
    """For each sequence (prompt + served reply, right-padded to one
    length) and each next token, how far the reference's logit of that
    token lies under the reference's own best logit at that position:
    (shortfall [B, S-1], std of the logits). Row b column t judges
    token seqs[b, t+1], or `tokens[b, t]` where another chooser's
    tokens are judged along the same sequences (the control). Causal
    (a block's keys by its mask; a summary is seen only from the block
    after its chunk, and a chunk that holds padding lies in or past the
    last real block), so the padding changes nothing before it.
    Computed on the device; only [B, S] floats come back."""
    seqs = jnp.asarray(seqs)
    tokens = seqs[:, 1:] if tokens is None else jnp.asarray(tokens)
    return _shortfall(logits(states, seqs, **arch), tokens)


def lower_precision_choice(states, seqs, lower, **arch):
    """[B, S-1] greedy next tokens along `seqs` of this reference with
    every matrix and every matrix product's input rounded to `lower`:
    what a tier computed one precision below the configuration's would
    serve. `reference/evabyte_control.py` has `served_shortfall` judge
    them as it judges the served tokens."""
    return logits(states, seqs, lower, **arch)[:, :-1].argmax(-1)
