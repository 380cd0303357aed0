"""The routed expert layer every mixture-of-experts model of the decode
tier calls: a router over all `n_experts` that scores by one of two
rules, top-k chosen, weighted by the chosen scores normalised, and the
products of the experts this chip HOLDS.

    "sigmoid":  sig = sigmoid(x W_r) in float32;  S = top_k(sig + b)
    "softmax":  sig = softmax(x W_r) over all experts, float32, no
                bias;  S = top_k(sig)
    w_e = sig_e / (sum_S sig + sum_eps)
    y   = scale * sum_{e in S and held} w_e act(x G_e, x U_e) D_e

`act(g, u)` is silu(g) * u unless an architecture names another
(`swigluoai`), and `scale` its `routed_scaling_factor` (1: none).

`held = (first, count)` names the held experts: `ffn["W_g"]`, `W_u`
([count, d, f]) and `W_d` ([count, f, d]) are theirs alone, while
`W_r` [d, n_experts] and `b` [n_experts] (the sigmoid rule's alone)
keep the router's width. The layer drops no token at any imbalance.
What differs between architectures is numbers and the scoring rule,
not code: how many experts and how many a token (the router's
shapes), `sum_eps` in the normalising sum, the activation and the
scale. A shared expert, gated or not, is the model's own
(`BlockSparseMoELM` adds an ungated one, `GatedDeltaMoELM` one with a
sigmoid gate a token): every chip computes it whole.

Two product paths, chosen by the row count (static): up to
`dense_rows` rows every held expert runs over every row (one dense
product a matrix: the weights' bytes bound it, and they meet the
sorted path's multiply-adds at about 240 rows whatever the widths);
above, assignments are sorted by expert and multiplied group by group
(`ragged_dot`).
"""
from __future__ import annotations

import functools

# rows up to which every held expert runs over every row
DENSE_ROWS = 256


def swigluoai(g, u, alpha=1.702, limit=7.0):
    """gpt-oss's clamped gate: min(g, limit) * sigmoid(alpha * min(g,
    limit)) * (clip(u, -limit, limit) + 1), in float32, returned in
    g's dtype."""
    import jax
    import jax.numpy as jnp

    gf = jnp.minimum(g.astype(jnp.float32), limit)
    uf = jnp.clip(u.astype(jnp.float32), -limit, limit)
    return (gf * jax.nn.sigmoid(alpha * gf) * (uf + 1.0)).astype(g.dtype)


def routed_experts(ffn, x, prec, *, held, experts_per_token,
                   dense_rows=DENSE_ROWS, sum_eps=0.0, act=None, scale=1.0,
                   scoring="sigmoid"):
    """The held experts' part of a routed layer for x [N, d], and the
    held experts' assignment counts [count] (from which a step's three
    counters come: their sum, how many are not zero, their maximum).
    `act` None is silu(g) * u; `scale` multiplies the held part;
    `scoring` is "sigmoid" (with the bias `ffn["b"]`) or "softmax"."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    first, E = held
    K = experts_per_token
    N = x.shape[0]
    all_held = first == 0 and E == ffn["W_r"].shape[-1]
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring {scoring!r}: sigmoid or softmax")
    with jax.named_scope("moe_router"):
        logits = jnp.matmul(
            x.astype(jnp.float32), ffn["W_r"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST)
        if scoring == "sigmoid":
            sig = jax.nn.sigmoid(logits)
            _, idx = lax.top_k(sig + ffn["b"], K)          # [N,K]
        else:
            sig = jax.nn.softmax(logits, -1)
            _, idx = lax.top_k(sig, K)
        chosen = jnp.take_along_axis(sig, idx, -1)
        total = jnp.sum(chosen, -1, keepdims=True)
        if sum_eps:
            total = total + sum_eps
        w = chosen / total
        local = idx - first
        here = (local >= 0) & (local < E)
        local = jnp.where(here, local, E)                 # E: elsewhere
        counts = jnp.zeros(E + 1, jnp.int32).at[
            local.reshape(-1)].add(1)[:E]
    with jax.named_scope("moe_experts"):
        if N <= dense_rows:
            # every held expert over every row, weighted by the
            # row's share for it (0 where it was not chosen)
            cw = jnp.sum(jax.nn.one_hot(local, E + 1, dtype=jnp.float32)
                         [..., :E] * w[..., None], 1)      # [N,E]
            g = jnp.einsum("nd,edf->enf", x, ffn["W_g"], precision=prec)
            u = jnp.einsum("nd,edf->enf", x, ffn["W_u"], precision=prec)
            a = (jax.nn.silu(g) * u if act is None else act(g, u)) \
                * cw.T[:, :, None].astype(x.dtype)
            y = jnp.einsum("enf,efd->nd", a, ffn["W_d"], precision=prec)
            return _scaled(y, scale), counts
        # assignments sorted by expert (those routed elsewhere
        # last), each group through its expert
        flat = local.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        ws = jnp.where(here, w, 0.0).reshape(-1)
        at = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * K, dtype=order.dtype)).reshape(N, K)
        rd = functools.partial(lax.ragged_dot, group_sizes=counts,
                               precision=prec)

        def through(rows):
            """The first `rows` sorted assignments (static; they
            hold every local one) through their experts and back
            to their tokens."""
            o = order[:rows]
            xs = x[o // K]
            if act is None:
                a = jax.nn.silu(rd(xs, ffn["W_g"])) * rd(xs, ffn["W_u"])
            else:
                a = act(rd(xs, ffn["W_g"]), rd(xs, ffn["W_u"]))
            y = rd(a, ffn["W_d"])                          # [rows,d]
            # rows past the held groups were multiplied by no
            # expert: whatever they hold is replaced, never weighted
            y = jnp.where((flat[o] < E)[:, None],
                          y * ws[o][:, None].astype(y.dtype), 0)
            y = jnp.where((at < rows)[..., None],
                          y[jnp.minimum(at, rows - 1)], 0)  # [N,K,d]
            return y.astype(jnp.float32).sum(1).astype(x.dtype)

        if all_held:
            # every assignment is local: straight through all of them
            return _scaled(through(N * K), scale), counts
        # where the held experts are a small share of all and get
        # their share of the assignments, a quarter of the rows
        # carries the local ones at a quarter of the gathers; N*K
        # rows hold any imbalance, so nothing is dropped
        few = N * K // 4
        y = lax.cond(counts.sum() <= few, lambda: through(few),
                     lambda: through(N * K))
        return _scaled(y, scale), counts


def _scaled(y, scale):
    return y if scale == 1.0 else y * scale
