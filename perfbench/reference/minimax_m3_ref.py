"""Plain reference of the MiniMax-M3 language model's block
(huggingface.co/MiniMaxAI/MiniMax-M3 `config.json`; its attention as
the catalog describes it: "per-GQA-group 4-head indexer scores
128-token max-pooled KV blocks, top-16 blocks + first/local"):
Gemma RMSNorm (gain 1 + g), per-head q/k RMSNorm, partial rotary,
learned block-sparse attention, a swigluoai MLP (dense in the first
layer; elsewhere a sigmoid-routed layer of which the held experts are
computed, scaled by `routed_scaling_factor`, beside an ungated shared
expert). G groups of Hg query heads, N = `block`:

    pooled key of block b (complete):  Kb[b,g] = max_{s in b} kI[s,g]
    sig[t,g,b] = sum_j wI[t,g,j] relu(qI[t,g,j] . Kb[b,g])
    query t, c = t // N: block 0, blocks c - local + 1 .. c, and the
    `top` best of blocks 1 .. c - local by sig (ties: the lower index)
    o[t,h] = softmax over s <= t in those blocks of q.k / sqrt(D), of v

float32 `jax.numpy`, matmuls at "highest" precision, no cache, no
kernels: every query's selection is computed from the full sequence's
pooled keys, ranked by counting (not `lax.top_k`), and applied as an
explicit mask over every key. It shares no code with the program: it
is given the program's weights by name (`Model.get_states()`) and the
architecture's numbers, the same held range of experts among them.

So that 28,672 positions at the published widths fit beside the served
model (11.8 GB of a 16 GB chip), a layer runs as programs of its own
(`jitted=True`): the keys, values and indexer keys of every position a
row chunk at a time, the attention `QUERIES` queries and one group at a
time, every product a row chunk at a time, each routed expert a
program of its own; a weight is cast to float32 where it is used.

Departures from the published description are listed under `assumed`
in configs/minimax-m3.json: the indexer's width, its own projections
from the normed input, the weighted-ReLU score, no rotary on its keys,
the max-pool over complete blocks, "local" as the query's own block and
the one before, rotate-half pairing, per-head norm gains [D],
normalised top-k weights and an ungated shared expert.
"""
import math

import jax
import jax.numpy as jnp

PREFIX = "BlockSparseMoELM"
QUERIES = 128      # queries a piece of the attention
ROWS = 2048        # rows a piece of a product


def _arch(kw):
    """The keyword arguments as one hashable, static value."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in kw.items()))


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


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


def _act(g, u, alpha, limit):
    g = jnp.minimum(g, limit)
    return g * jax.nn.sigmoid(alpha * g) * (jnp.clip(u, -limit, limit) + 1.0)


def _low(lower):
    return (lambda t: t) if lower is None else (
        lambda t: t.astype(lower).astype(jnp.float32))


def _matrix(t, low):
    """A stored matrix in float32, rounded for the control."""
    return low(jnp.asarray(t, jnp.float32))


def _rows(fn, *xs, at_once=ROWS):
    """fn over arrays xs [S, ...] alike, `at_once` rows at a time (one
    piece after another, `lax.map`) where S is a whole number of
    pieces."""
    S = xs[0].shape[0]
    if S <= at_once or S % at_once:
        return fn(*xs)

    def piece(t):
        return t.reshape(S // at_once, at_once, *t.shape[1:])

    out = jax.lax.map(lambda args: fn(*args), tuple(piece(x) for x in xs))
    return jax.tree_util.tree_map(
        lambda t: t.reshape(S, *t.shape[2:]), out)


def _split(a):
    """Columns of W_qkv and of the indexer's W (group-major)."""
    H, G, D = a["num_heads"], a["kv_heads"], a["head_dim"]
    J, Di = a["index_heads"], a["index_dim"]
    return (H * D, G * D, G * D), (G * J * Di, G * Di, G * J)


def keys_layer(h, pos, ws, arch, lower=None):
    """k [S, G, D] (normed, rotated), v [S, G, D] and the indexer's kI
    [S, G, Di] of every position of h [S, d]; ws = (ln1, W_qkv, k_norm,
    W_index) as stored."""
    a, low = dict(arch), _low(lower)
    g, W_qkv, k_norm, W_i = ws
    (nq, nk, nv), (nqi, nki, _) = _split(a)
    G, D, Di = a["kv_heads"], a["head_dim"], a["index_dim"]
    with jax.default_matmul_precision("highest"):
        def piece(r):
            x = low(_rms(r, g, a["norm_eps"]))
            kv = x @ _matrix(W_qkv[:, nq:], low)
            ki = x @ _matrix(W_i[:, nqi:nqi + nki], low)
            return jnp.concatenate([kv, ki], -1)
        out = _rows(piece, h)
    S = h.shape[0]
    k = _rms(out[:, :nk].reshape(S, G, D), k_norm, a["norm_eps"])
    k = low(_rope(k, pos, a["rope_theta"], a["rotary_dim"]))
    return (k, low(out[:, nk:nk + nv].reshape(S, G, D)),
            low(out[:, nk + nv:].reshape(S, G, Di)))


def pooled_keys(ki, block):
    """Kb [nb, G, Di]: each block's elementwise max of kI [S, G, Di]
    (a block the sequence ends inside is never ranked)."""
    S, G, Di = ki.shape
    nb = -(-S // block)
    ki = jnp.pad(ki, ((0, nb * block - S), (0, 0), (0, 0)),
                 constant_values=-jnp.inf)
    return ki.reshape(nb, block, G, Di).max(1)


def selection(qi, wi, kb, t, arch):
    """[Q, G, nb] bool: the blocks the queries at positions t [Q] read,
    from their qI [Q, G, J, Di], wI [Q, G, J] and the pooled keys kb
    [nb, G, Di]. Rank by counting: a candidate is picked where fewer
    than `top` candidates outscore it (an equal score at a lower index
    outscores)."""
    a = dict(arch)
    N, L, top = a["block"], a["local_blocks"], a["top_blocks"]
    nb = kb.shape[0]
    with jax.default_matmul_precision("highest"):
        sig = jnp.einsum("qgj,qgjb->qgb", wi, jax.nn.relu(
            jnp.einsum("qgjd,bgd->qgjb", qi, kb)))
    c = (t // N)[:, None, None]
    b = jnp.arange(nb)[None, None, :]
    cand = (b >= 1) & (b <= c - L)
    above = ((sig[..., None, :] > sig[..., :, None])
             | ((sig[..., None, :] == sig[..., :, None])
                & (b[..., None, :] < b[..., :, None])))     # [Q,G,b,b']
    rank = jnp.sum(above & cand[..., None, :], -1)
    return (b == 0) | ((b <= c) & (b > c - L)) | (cand & (rank < top))


def _picked_ids(sel, width):
    """The ids of a selection [Q, G, nb] ascending, padded with -1 to
    `width`."""
    nb = sel.shape[-1]
    key = jnp.where(sel, jnp.arange(nb), nb)
    ids = jnp.sort(key, -1)[..., :width]
    return jnp.where(ids < nb, ids, -1)


def attention_layer(h, k, v, ki, pos, ws, arch, lower=None, picks=False):
    """h += Attn(RMSNorm(h)) for h [S, d] at positions pos [S], given
    every position's k, v and kI (`keys_layer`); ws = (ln1, W_qkv,
    q_norm, W_index, W_o) as stored. With `picks`, also the ids of the
    blocks each (position, group) read [S, G, 1 + local + top]."""
    a, low = dict(arch), _low(lower)
    g, W_qkv, q_norm, W_i, W_o = ws
    (nq, _, _), (nqi, nki, nwi) = _split(a)
    H, G, D = a["num_heads"], a["kv_heads"], a["head_dim"]
    J, Di, N = a["index_heads"], a["index_dim"], a["block"]
    S = h.shape[0]
    Q = QUERIES if S % QUERIES == 0 else S
    kb = pooled_keys(ki, N)
    width = min(1 + a["local_blocks"] + a["top_blocks"], -(-S // N))

    def queries(args):
        hq, t = args                                   # [Q, d], [Q]
        with jax.default_matmul_precision("highest"):
            x = low(_rms(hq, g, a["norm_eps"]))
            q = _rms((x @ _matrix(W_qkv[:, :nq], low)).reshape(-1, H, D),
                     q_norm, a["norm_eps"])
            q = low(_rope(q, t, a["rope_theta"], a["rotary_dim"]))
            qi = low(x @ _matrix(W_i[:, :nqi], low)).reshape(-1, G, J, Di)
            wi = low(x @ _matrix(W_i[:, nqi + nki:nqi + nki + nwi], low)
                     ).reshape(-1, G, J)
        sel = selection(qi, wi, kb, t, arch)           # [Q, G, nb]
        ok = (jnp.repeat(sel, N, -1)[..., :S]
              & (jnp.arange(S)[None, None, :] <= t[:, None, None]))

        def group(args):
            qg, kg, vg, okg = args      # [Q,Hg,D], [S,D], [S,D], [Q,S]
            with jax.default_matmul_precision("highest"):
                s = jnp.einsum("qhd,sd->qhs", qg, kg) / math.sqrt(D)
                p = low(jax.nn.softmax(
                    jnp.where(okg[:, None, :], s, -jnp.inf), -1))
                return jnp.einsum("qhs,sd->qhd", p, vg)

        o = jax.lax.map(group, (q.reshape(-1, G, H // G, D).swapaxes(0, 1),
                                k.swapaxes(0, 1), v.swapaxes(0, 1),
                                ok.swapaxes(0, 1)))
        o = low(o.swapaxes(0, 1).reshape(-1, H * D))
        with jax.default_matmul_precision("highest"):
            out = hq + o @ _matrix(W_o, low)
        return out, _picked_ids(sel, width)

    out, ids = jax.lax.map(queries, (h.reshape(S // Q, Q, -1),
                                     pos.reshape(S // Q, Q)))
    out = out.reshape(S, -1)
    return (out, ids.reshape(S, G, width)) if picks else out


def dense_layer(h, ws, arch, lower=None):
    """h += act(n W_g, n W_u) W_d, n = RMSNorm(h); ws = (ln2, W_g, W_u,
    W_d) as stored."""
    a, low = dict(arch), _low(lower)
    g, W_g, W_u, W_d = ws
    with jax.default_matmul_precision("highest"):
        def piece(r):
            n = low(_rms(r, g, a["norm_eps"]))
            return r + low(_act(n @ _matrix(W_g, low), n @ _matrix(W_u, low),
                                a["swiglu_alpha"], a["swiglu_limit"])
                           ) @ _matrix(W_d, low)
        return _rows(piece, h)


def router_weights(h, ws, arch):
    """The weight each HELD expert gets at each row of h [S, d] (0
    where the row did not choose it), times `routed_scale` [S, count]:
    sigmoid scores of the normed input in float32, top-k by score +
    bias, normalised over the chosen; ws = (ln2, W_r, b)."""
    a = dict(arch)
    g, W_r, bias = ws
    with jax.default_matmul_precision("highest"):
        sig = jax.nn.sigmoid(_rms(h, g, a["norm_eps"])
                             @ jnp.asarray(W_r, jnp.float32))
    _, idx = jax.lax.top_k(sig + bias, a["experts_per_token"])
    chosen = jnp.take_along_axis(sig, idx, -1)
    w = chosen / jnp.sum(chosen, -1, keepdims=True)
    first, count = a["held"]
    full = jnp.zeros_like(sig).at[jnp.arange(sig.shape[0])[:, None],
                                  idx].set(w)
    return a["routed_scale"] * full[:, first:first + count]


def expert_layer(y, h, w, ws, arch, lower=None):
    """y += w[:, None] * act(n W_g, n W_u) W_d, n = RMSNorm(h): one
    expert over every row of h [S, d], weighted by w [S] (a routed
    expert's weights, or ones for the shared one); ws = (ln2, W_g, W_u,
    W_d) as stored."""
    a, low = dict(arch), _low(lower)
    g, W_g, W_u, W_d = ws
    with jax.default_matmul_precision("highest"):
        def piece(yr, hr, wr):
            n = low(_rms(hr, g, a["norm_eps"]))
            return yr + wr[:, None] * (low(_act(
                n @ _matrix(W_g, low), n @ _matrix(W_u, low),
                a["swiglu_alpha"], a["swiglu_limit"])) @ _matrix(W_d, low))
        return _rows(piece, y, h, w)


def head_layer(h, ws, arch, lower=None):
    """[S, vocab] logits of h [S, d]."""
    a, low = dict(arch), _low(lower)
    g, W_head = ws
    with jax.default_matmul_precision("highest"):
        return low(_rms(h, g, a["norm_eps"])) @ _matrix(W_head, low)


def head_reading(h, tokens, ws, arch, lower=None):
    """What a check reads of the logits of h [S, d], a row chunk at a
    time so that [S, vocab] never exists: each row's best logit, its
    logit of tokens [S], its argmax, and the logits' sum and sum of
    squares."""
    def piece(hr, tr):
        lg = head_layer(hr, ws, arch, lower)
        return (lg.max(-1), jnp.take_along_axis(lg, tr[:, None], -1)[:, 0],
                lg.argmax(-1).astype(jnp.int32), lg.sum(-1),
                (lg * lg).sum(-1))
    return _rows(piece, h, tokens, at_once=QUERIES)


_JITTED = {
    keys_layer: jax.jit(keys_layer, static_argnames=("arch", "lower")),
    attention_layer: jax.jit(attention_layer,
                             static_argnames=("arch", "lower", "picks")),
    dense_layer: jax.jit(dense_layer, static_argnames=("arch", "lower"),
                         donate_argnums=0),
    router_weights: jax.jit(router_weights, static_argnames=("arch",)),
    # the running sum is updated where it lies
    expert_layer: jax.jit(expert_layer, static_argnames=("arch", "lower"),
                          donate_argnums=0),
    head_reading: jax.jit(head_reading, static_argnames=("arch", "lower")),
}


def forward(states, ids, arch, lower=None, jitted=False, picks=False):
    """[S] token ids -> (the final hidden states [S, d], and each
    layer's selected block ids [S, G, width], -1 past a count, or None
    without `picks`). `lower` names a dtype below the configuration's
    for the lower-precision control: every matrix and every matrix
    product's input is rounded to it; sums stay float32 (the router's
    product is left in float32). `jitted`: each piece as a program of
    its own."""
    a = dict(arch)
    if jitted:
        def run(fn, *args, **kw):
            return _JITTED[fn](*args, arch=arch, **kw)
    else:
        def run(fn, *args, **kw):
            return fn(*args, arch, **kw)

    def w(*names):
        return tuple(states[f"{PREFIX}.{name}"] for name in names)

    pos = jnp.arange(ids.shape[0])
    h = _matrix(w("embed.W")[0], _low(lower))[ids]
    low_kw = {} if lower is None else {"lower": lower}
    chosen = []
    for li, routed in enumerate(a["moe_layers"]):
        pre = f"blocks.l{li}"
        k, v, ki = run(keys_layer, h, pos, w(
            f"{pre}.ln1.g", f"{pre}.attn.W_qkv", f"{pre}.attn.k_norm",
            f"{pre}.index.W"), **low_kw)
        out = run(attention_layer, h, k, v, ki, pos, w(
            f"{pre}.ln1.g", f"{pre}.attn.W_qkv", f"{pre}.attn.q_norm",
            f"{pre}.index.W", f"{pre}.attn.W_o"), picks=picks, **low_kw)
        h, picked = out if picks else (out, None)
        chosen.append(picked)
        if not routed:
            h = run(dense_layer, h, w(f"{pre}.ln2.g", f"{pre}.mlp.W_g",
                                      f"{pre}.mlp.W_u", f"{pre}.mlp.W_d"),
                    **low_kw)
            continue
        (g,) = w(f"{pre}.ln2.g")
        wts = run(router_weights, h, w(f"{pre}.ln2.g", f"{pre}.moe.W_r",
                                       f"{pre}.moe.b"))
        W_g, W_u, W_d = w(f"{pre}.moe.W_g", f"{pre}.moe.W_u",
                          f"{pre}.moe.W_d")
        y = run(expert_layer, jnp.zeros_like(h), h, jnp.ones_like(h[:, 0]),
                (g,) + w(f"{pre}.shared.W_g", f"{pre}.shared.W_u",
                         f"{pre}.shared.W_d"), **low_kw)
        for e in range(a["held"][1]):
            y = run(expert_layer, y, h, wts[:, e], (g, W_g[e], W_u[e], W_d[e]),
                    **low_kw)
        h = h + y
    return h, (chosen if picks else None)


def _forward(states, row, arch, lower=None, picks=False):
    return forward(states, jnp.asarray(row), arch, lower, jitted=True,
                   picks=picks)


def _head(states):
    return tuple(states[f"{PREFIX}.{n}"] for n in ("ln_f.g", "head.W"))


def logits(states, ids, lower=None, **arch):
    """[B, S, vocab] logits, float32, a sequence at a time (a toy's:
    at the cell's length the logits of a sequence alone are 2.9 GB,
    and `served_shortfall` reads them a row chunk at a time)."""
    arch = _arch(arch)
    return jnp.stack([head_layer(_forward(states, row, arch, lower)[0],
                                 _head(states), arch, lower) for row in ids])


def picks(states, ids, **arch):
    """Each layer's selected block ids [B, S, G, width] (-1 past a
    row's count), along sequences ids [B, S]."""
    arch = _arch(arch)
    out = [_forward(states, row, arch, picks=True)[1] for row in ids]
    return [jnp.stack(layer) for layer in zip(*out)]


def _reading(states, seqs, tokens, lower, arch):
    """Per sequence, `head_reading` along it for the next tokens."""
    arch = _arch(arch)
    out = []
    for row, tok in zip(jnp.asarray(seqs), tokens):
        h, _ = _forward(states, row, arch, lower)
        out.append(_JITTED[head_reading](h[:-1], tok, _head(states),
                                         arch=arch, lower=lower))
    return [jnp.stack(t) for t in zip(*out)]


def served_shortfall(states, seqs, tokens=None, **arch):
    """For each sequence (prompt + served reply, right-padded to one
    length) and each next token, how far the reference's logit of that
    token lies under the reference's own best logit at that position:
    (shortfall [B, S-1], std of the logits). Row b column t judges
    token seqs[b, t+1], or `tokens[b, t]` where another chooser's
    tokens are judged along the same sequences (the control). Causal,
    and a block's pooled key is ranked only once the block is complete
    and behind the query's local blocks, so the padding changes nothing
    before it. Computed on the device; only [B, S] numbers come back."""
    seqs = jnp.asarray(seqs)
    tokens = seqs[:, 1:] if tokens is None else jnp.asarray(tokens)
    best, got, _, total, squares = _reading(states, seqs, tokens, None, arch)
    n = total.size * states[f"{PREFIX}.head.W"].shape[1]
    mean = total.sum() / n
    return best - got, jnp.sqrt(squares.sum() / n - mean * mean)


def lower_precision_choice(states, seqs, lower, **arch):
    """[B, S-1] greedy next tokens along `seqs` of this reference with
    every matrix and every matrix product's input rounded to `lower`:
    what a tier computed one precision below the configuration's would
    serve. `reference/minimax_m3_control.py` has `served_shortfall`
    judge them as it judges the served tokens."""
    seqs = jnp.asarray(seqs)
    return _reading(states, seqs, seqs[:, 1:], lower, arch)[2]


def selection_disagreement(program, reference):
    """Share of (row, position, layer, group) whose selected block sets
    differ between two lists of per-layer ids [B, S, G, width], each
    padded past its count (the program with 0, the reference with -1)
    and compared by set: both are ascending."""
    differ, total = 0, 0
    for p, r in zip(program, reference):
        p, r = jnp.asarray(p), jnp.asarray(r)
        n = jnp.sum(r >= 0, -1, keepdims=True)
        here = jnp.arange(r.shape[-1]) < n
        same = jnp.all(jnp.where(here, p == r, True), -1) & (
            jnp.sum(jnp.where(here, 0, p), -1) == 0)
        differ += int(jnp.sum(~same))
        total += same.size
    return differ / total
