"""Plain reference of the Qwen3-Next language model's block
(huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct `config.json`,
`model_type` `qwen3_next`; its layers as transformers'
`modeling_qwen3_next.py` writes them): zero-centred RMSNorm (gain
1 + g), three Gated DeltaNet layers to one gated full-attention layer,
and in every layer a softmax-routed layer of which the held experts
are computed, beside a shared expert with a sigmoid gate a token.

    Gated DeltaNet, per value head h (key head h // r), from S = 0:
        S <- exp(g_t) S;  S <- S + k_t (x) beta_t (v_t - S^T k_t)
        o_t = S^T q_t
    full:  (softmax(q.k / sqrt(D)) v, causal, grouped) * sigmoid(gate)
    MoE:   p = softmax(x W_r);  S = top-k(p);  w_e = p_e / sum_S p

float32 `jax.numpy`, matmuls at "highest" precision, no cache, no
kernels, no chunks: the delta rule is the per-position recurrence
above, a `lax.scan` over every position. It shares no code with the
program: it is given the program's weights by name
(`Model.get_states()`) and the architecture's numbers, the same held
range of experts and vocabulary slice among them.

So that 4,096 positions at the published widths fit beside the served
model, a layer runs as programs of its own: the attention `QUERIES`
queries at a time, each routed expert a program, the logits a row
chunk at a time; a weight is cast to float32 where it is used.

Departures from the published description are listed under `assumed`
in configs/qwen3-next-80b-a3b.json: the decay draw, the rotate-half
pairing, and the gated norm's eps (the model's rms_norm_eps).
"""
import math

import jax
import jax.numpy as jnp

PREFIX = "GatedDeltaMoELM"
QUERIES = 256      # queries a piece of the attention
ROWS = 512         # rows a piece of the head's reading


def _arch(kw):
    """The keyword arguments as one hashable, static value."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in kw.items()))


def _rms(x, g, eps, centred=True):
    """x / rms(x) times (1 + g), or times g where not `centred`."""
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + g if centred else g)


def _rope(x, pos, theta, R):
    """x [S, H, D] at positions pos [S]: rotate-half over the first R
    dims, the rest as they are."""
    half = R // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / R)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:R]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., R:]],
                           -1)


def _low(lower):
    return (lambda t: t) if lower is None else (
        lambda t: t.astype(lower).astype(jnp.float32))


def _matrix(t, low):
    """A stored matrix in float32, rounded for the control."""
    return low(jnp.asarray(t, jnp.float32))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """o [S, H, Dv] of the per-position recurrence from a zero state,
    for q, k [S, H, Dk], v [S, H, Dv], g and beta [S, H]; and the state
    after the last position [H, Dk, Dv]."""
    H, Dk, Dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[:, None, None]
        kv = jnp.einsum("hkv,hk->hv", S, kt)
        S = S + kt[:, :, None] * (bt[:, None] * (vt - kv))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    with jax.default_matmul_precision("highest"):
        S, o = jax.lax.scan(step, jnp.zeros((H, Dk, Dv), jnp.float32),
                            (q, k, v, g, beta))
    return o, S


def linear_layer(h, ws, arch, lower=None):
    """(h + GatedDeltaNet(N(h)), the layer's state after the last
    position [Hv, Dk, Dv]) for h [S, d]; ws = (ln1, W_qkvz, W_ba, conv,
    A_log, dt_bias, norm, W_out) as stored."""
    a, low = dict(arch), _low(lower)
    ln1, W_qkvz, W_ba, conv, A_log, dt_bias, norm, W_out = ws
    Hk, Hv = a["lin_k_heads"], a["lin_v_heads"]
    Dk, Dv, L = a["lin_k_dim"], a["lin_v_dim"], a["conv_L"]
    r, S = Hv // Hk, h.shape[0]
    with jax.default_matmul_precision("highest"):
        x = low(_rms(h, ln1, a["norm_eps"]))
        qkvz = (x @ _matrix(W_qkvz, low)).reshape(S, Hk, -1)
        ba = (x @ _matrix(W_ba, low)).reshape(S, Hk, 2 * r)
    q, k = qkvz[..., :Dk], qkvz[..., Dk:2 * Dk]
    v = qkvz[..., 2 * Dk:2 * Dk + r * Dv].reshape(S, Hv, Dv)
    z = qkvz[..., 2 * Dk + r * Dv:].reshape(S, Hv, Dv)
    b, a_ = ba[..., :r].reshape(S, Hv), ba[..., r:].reshape(S, Hv)
    u = jnp.concatenate([q.reshape(S, -1), k.reshape(S, -1),
                         v.reshape(S, -1)], -1)
    up = jnp.pad(u, ((L - 1, 0), (0, 0)))
    w = _matrix(conv, low)
    c = jax.nn.silu(sum(w[j] * up[j:j + S] for j in range(L)))
    q = jnp.repeat(c[:, :Hk * Dk].reshape(S, Hk, Dk), r, axis=1)
    k = jnp.repeat(c[:, Hk * Dk:2 * Hk * Dk].reshape(S, Hk, Dk), r, axis=1)
    v = c[:, 2 * Hk * Dk:].reshape(S, Hv, Dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(A_log) * jax.nn.softplus(a_ + dt_bias)
    o, state = delta_rule(_l2(q) / math.sqrt(Dk), _l2(k), v, g, beta)
    o = _rms(o, norm, a["norm_eps"], centred=False) * jax.nn.silu(z)
    with jax.default_matmul_precision("highest"):
        return h + low(o.reshape(S, Hv * Dv)) @ _matrix(W_out, low), state


def full_layer(h, pos, ws, arch, lower=None):
    """h += GatedAttention(N(h)) for h [S, d] at positions pos [S];
    ws = (ln1, W_qkv, q_norm, k_norm, W_o) as stored."""
    a, low = dict(arch), _low(lower)
    ln1, W_qkv, q_norm, k_norm, W_o = ws
    H, G, D = a["num_heads"], a["kv_heads"], a["head_dim"]
    S, eps = h.shape[0], a["norm_eps"]
    with jax.default_matmul_precision("highest"):
        proj = low(_rms(h, ln1, eps)) @ _matrix(W_qkv, low)
    qg = proj[:, :2 * H * D].reshape(S, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = proj[:, 2 * H * D:(2 * H + G) * D].reshape(S, G, D)
    v = low(proj[:, (2 * H + G) * D:].reshape(S, G, D))
    q = low(_rope(_rms(q, q_norm, eps), pos, a["rope_theta"], a["rotary_dim"]))
    k = low(_rope(_rms(k, k_norm, eps), pos, a["rope_theta"], a["rotary_dim"]))
    Q = QUERIES if S % QUERIES == 0 else S
    kv = jnp.repeat(jnp.arange(G), H // G)              # a query head's group

    def queries(args):
        qc, t = args                                   # [Q, H, D], [Q]
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("qhd,shd->hqs", qc, k[:, kv]) / math.sqrt(D)
            s = jnp.where(jnp.arange(S)[None, None, :] <= t[None, :, None],
                          s, -jnp.inf)
            return jnp.einsum("hqs,shd->qhd", low(jax.nn.softmax(s, -1)),
                              v[:, kv])

    o = jax.lax.map(queries, (q.reshape(S // Q, Q, H, D),
                              pos.reshape(S // Q, Q))).reshape(S, H, D)
    o = low(o * jax.nn.sigmoid(gate))
    with jax.default_matmul_precision("highest"):
        return h + o.reshape(S, H * D) @ _matrix(W_o, low)


def router_weights(h, ws, arch):
    """The weight each HELD expert gets at each row of h [S, d] (0
    where the row did not choose it) [S, count]: softmax over every
    expert of the normed input's scores in float32, top-k, normalised
    over the chosen; ws = (ln2, W_r)."""
    a = dict(arch)
    g, W_r = ws
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(_rms(h, g, a["norm_eps"])
                           @ jnp.asarray(W_r, jnp.float32), -1)
    _, idx = jax.lax.top_k(p, a["experts_per_token"])
    chosen = jnp.take_along_axis(p, idx, -1)
    w = chosen / jnp.sum(chosen, -1, keepdims=True)
    first, count = a["held"]
    full = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], idx].set(w)
    return full[:, first:first + count]


def expert_layer(y, h, w, ws, arch, lower=None):
    """y += w[:, None] * (silu(n W_g) * n W_u) W_d, n = N(h): one expert
    over every row of h [S, d] weighted by w [S] (a routed expert's
    weights; for the shared one its gate, sigmoid(n W_s)); ws = (ln2,
    W_g, W_u, W_d) as stored."""
    a, low = dict(arch), _low(lower)
    g, W_g, W_u, W_d = ws
    with jax.default_matmul_precision("highest"):
        n = low(_rms(h, g, a["norm_eps"]))
        act = jax.nn.silu(n @ _matrix(W_g, low)) * (n @ _matrix(W_u, low))
        return y + w[:, None] * (low(act) @ _matrix(W_d, low))


def shared_gate(h, ws, arch, lower=None):
    """sigmoid(n w_s) [S] of the shared expert, n = N(h); ws = (ln2,
    gate [d, 1])."""
    a, low = dict(arch), _low(lower)
    g, W = ws
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(low(_rms(h, g, a["norm_eps"]))
                              @ _matrix(W, low))[:, 0]


def head_reading(h, tokens, ws, arch, lower=None):
    """What a check reads of the logits of h [S, d], a row chunk at a
    time so that [S, vocab] never exists: each row's best logit, its
    logit of tokens [S], its argmax, and the logits' sum and sum of
    squares."""
    a, low = dict(arch), _low(lower)
    g, W_head = ws

    def piece(args):
        hr, tr = args
        with jax.default_matmul_precision("highest"):
            lg = low(_rms(hr, g, a["norm_eps"])) @ _matrix(W_head, low)
        return (lg.max(-1), jnp.take_along_axis(lg, tr[:, None], -1)[:, 0],
                lg.argmax(-1).astype(jnp.int32), lg.sum(-1),
                (lg * lg).sum(-1))

    S = h.shape[0]
    R = ROWS if S % ROWS == 0 else S
    out = jax.lax.map(piece, (h.reshape(S // R, R, -1),
                              tokens.reshape(S // R, R)))
    return tuple(t.reshape(S) for t in out)


_JIT = {fn: jax.jit(fn, static_argnames=("arch", "lower"))
        for fn in (linear_layer, full_layer, shared_gate, head_reading)}
_JIT[router_weights] = jax.jit(router_weights, static_argnames=("arch",))
# the running sum is updated where it lies
_JIT[expert_layer] = jax.jit(expert_layer, static_argnames=("arch", "lower"),
                             donate_argnums=0)


def forward(states, ids, arch, lower=None, kept=None):
    """[S] token ids -> the final hidden states [S, d] (before the
    final norm). `lower` names a dtype below the configuration's for
    the lower-precision control: every matrix and every matrix
    product's input is rounded to it; sums, the recurrence and the
    router stay float32. `kept`, a list, gets each Gated DeltaNet
    layer's state after the last position."""
    a = dict(arch)
    low_kw = {} if lower is None else {"lower": lower}

    def run(fn, *args, **kw):
        return _JIT[fn](*args, arch=arch, **kw)

    def w(*names):
        return tuple(states[f"{PREFIX}.{name}"] for name in names)

    pos = jnp.arange(ids.shape[0])
    h = _matrix(w("embed.W")[0], _low(lower))[ids]
    for li in range(a["num_layers"]):
        pre = f"blocks.l{li}"
        if (li + 1) % a["full_interval"]:
            h, state = run(linear_layer, h, w(
                f"{pre}.ln1.g", *(f"{pre}.lin.{n}" for n in (
                    "W_qkvz", "W_ba", "conv", "A_log", "dt_bias", "norm",
                    "W_out"))), **low_kw)
            if kept is not None:
                kept.append(state)
        else:
            h = run(full_layer, h, pos, w(
                f"{pre}.ln1.g", *(f"{pre}.attn.{n}" for n in (
                    "W_qkv", "q_norm", "k_norm", "W_o"))), **low_kw)
        (g,) = w(f"{pre}.ln2.g")
        wts = run(router_weights, h, w(f"{pre}.ln2.g", f"{pre}.moe.W_r"))
        W_g, W_u, W_d = w(f"{pre}.moe.W_g", f"{pre}.moe.W_u",
                          f"{pre}.moe.W_d")
        y = run(expert_layer, jnp.zeros_like(h), h,
                run(shared_gate, h, (g,) + w(f"{pre}.shared.gate"), **low_kw),
                (g,) + w(f"{pre}.shared.W_g", f"{pre}.shared.W_u",
                         f"{pre}.shared.W_d"), **low_kw)
        for e in range(a["held"][1]):
            y = run(expert_layer, y, h, wts[:, e], (g, W_g[e], W_u[e], W_d[e]),
                    **low_kw)
        h = h + y
    return h


def _head(states):
    return tuple(states[f"{PREFIX}.{n}"] for n in ("ln_f.g", "head.W"))


def logits(states, ids, lower=None, **arch):
    """[B, S, vocab] logits, float32, a sequence at a time (a toy's:
    `served_shortfall` reads them a row chunk at a time)."""
    arch = _arch(arch)
    a, low = dict(arch), _low(lower)
    g, W_head = _head(states)
    out = []
    for row in jnp.asarray(ids):
        h = forward(states, row, arch, lower)
        with jax.default_matmul_precision("highest"):
            out.append(low(_rms(h, g, a["norm_eps"])) @ _matrix(W_head, low))
    return jnp.stack(out)


def _reading(states, seqs, tokens, lower, arch):
    """Per sequence, `head_reading` along it for the next tokens."""
    arch = _arch(arch)
    out = []
    for row, tok in zip(jnp.asarray(seqs), tokens):
        h = forward(states, row, arch, lower)
        out.append(_JIT[head_reading](h[:-1], tok, _head(states), arch=arch,
                                      **({} if lower is None
                                         else {"lower": lower})))
    return [jnp.stack(t) for t in zip(*out)]


def served_shortfall(states, seqs, tokens=None, **arch):
    """For each sequence (prompt + served reply, right-padded to one
    length) and each next token, how far the reference's logit of that
    token lies under the reference's own best logit at that position:
    (shortfall [B, S-1], std of the logits). Row b column t judges
    token seqs[b, t+1], or `tokens[b, t]` where another chooser's
    tokens are judged along the same sequences (the control). Causal,
    so the padding changes nothing before it. Computed on the device;
    only [B, S] numbers come back."""
    seqs = jnp.asarray(seqs)
    tokens = seqs[:, 1:] if tokens is None else jnp.asarray(tokens)
    best, got, _, total, squares = _reading(states, seqs, tokens, None, arch)
    n = total.size * states[f"{PREFIX}.head.W"].shape[1]
    mean = total.sum() / n
    return best - got, jnp.sqrt(squares.sum() / n - mean * mean)


def recurrent_states(states, ids, **arch):
    """Each Gated DeltaNet layer's matrix state [Hv, Dk, Dv] float32
    after the last of ids [S], by the per-position recurrence:
    `reference/qwen3_next_control.py` holds the program's slab row to
    them."""
    kept = []
    forward(states, jnp.asarray(ids), _arch(arch), kept=kept)
    return kept


def lower_precision_choice(states, seqs, lower, **arch):
    """[B, S-1] greedy next tokens along `seqs` of this reference with
    every matrix and every matrix product's input rounded to `lower`:
    what a tier computed one precision below the configuration's would
    serve. `reference/qwen3_next_control.py` has `served_shortfall`
    judge them as it judges the served tokens."""
    seqs = jnp.asarray(seqs)
    return _reading(states, seqs, seqs[:, 1:], lower, arch)[2]
