"""The comparisons that decide `correct`. Each returns (ok, what it
measured) so the number is printed whether or not it passed."""
import math

import numpy as np


def logits_agree(got, ref, tol):
    """max|got - ref| <= tol * max|ref|, both finite. Error against the
    tensor's SCALE, not elementwise: a logit near zero has no relative
    error worth the name. Arrays may live on the device; two scalars
    come back."""
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    err = float(jnp.max(jnp.abs(got - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    ok = math.isfinite(err) and math.isfinite(scale) and err <= tol * scale
    return ok, err / scale if scale else float("inf")


def served_within_margin(shortfall, prompt_lens, total_lens, margin):
    """Every served token's reference logit lies within `margin` of the
    reference's best at its position. `shortfall[b, t]` judges token
    t+1 of row b; served positions are prompt_len .. total_len-1.
    Token equality is the wrong test on the MXU: at a near-tie the
    served path and the reference may order two logits differently,
    while a broken cache misses by whole units."""
    shortfall = np.asarray(shortfall)
    worst = 0.0
    for b, (p, n) in enumerate(zip(prompt_lens, total_lens)):
        row = shortfall[b, p - 1:n - 1]
        if not np.all(np.isfinite(row)):
            return False, float("nan")
        worst = max(worst, float(row.max(initial=0.0)))
    return worst <= margin, worst


def losses_fall(first_of_run, in_window):
    """Every loss read in the window is finite and the last is below
    the first of the run."""
    return (bool(in_window) and all(math.isfinite(v) for v in in_window)
            and in_window[-1] < first_of_run)


def replicas_identical(arrays):
    """Every array's addressable shards hold the same bytes (a
    replicated parameter after data-parallel steps). Returns (ok,
    number of arrays that differ)."""
    bad = 0
    for a in arrays:
        shards = [np.asarray(s.data) for s in a.addressable_shards]
        first = shards[0].tobytes()
        if any(s.tobytes() != first for s in shards[1:]):
            bad += 1
    return bad == 0, bad
