"""Pipeline parallelism over a mesh axis: GPipe and 1F1B schedules.

The reference has no pipeline parallelism (SURVEY.md §2.4 — DP is its
only strategy); this module is TPU-native surplus, completing the
tp/pp/dp/sp axis set the mesh trainer exposes. Design is the standard
JAX/TPU recipe (the scaling-book pipelining pattern):

  * homogeneous stages (e.g. transformer blocks) with their parameters
    STACKED on a leading `pipe` dim, sharded so chip i holds stage i;
  * the batch splits into M microbatches; each tick every chip applies
    its stage to the microbatch in flight and hands the activation to
    its neighbor with `lax.ppermute` (the transfer rides ICI and
    overlaps the next tick's compute);
  * the whole schedule is a `lax.scan` inside `shard_map`.

Two schedules (ISSUE 10):

  * **"gpipe"** — forward-only scan over M + P - 1 ticks; `jax.vjp`
    differentiates it, so the backward is automatically the reverse
    pipeline. Simple, but reverse-mode saves every tick's residuals:
    the fwd→bwd boundary stashes activations for ALL M microbatches
    per stage (the GPipe memory profile).
  * **"1f1b"** — a `jax.custom_vjp`: the forward pass runs the same
    forward-only scan (residuals = params + inputs only), and the
    backward runs ONE combined scan of 2(M + P - 1) ticks interleaving
    one-forward-one-backward per stage with warmup/steady/cooldown
    phases. Each stage keeps a RING BUFFER of P saved stage inputs —
    the in-flight window — and recomputes its stage forward inside the
    backward tick's `jax.vjp`, so peak liveness across the fwd→bwd
    boundary is bounded by the pipe depth P instead of M
    (`hlo_profile.peak_bytes_estimate` verifies the drop; the price is
    one extra stage forward per backward tick, μ-cuDNN's
    memory/recompute trade).

    Schedule grid: forward of microbatch k runs at stage s on tick
    2k + s; its backward runs on tick 2k + 2P - 1 - s. Forwards and
    backwards at one stage land on opposite tick parities, so no stage
    ever does both in one tick; microbatch k and k + P reuse ring slot
    k mod P with the write always after the read (stage s reads at
    2k + 2P - 1 - s < 2k + 2P + s, the slot-safety inequality).

Bubble fraction is (P-1)/(M+P-1): choose microbatches >= pipe size.
Parameter gradients come back stage-stacked, matching the input
layout, so the optimizer update is uniform across chips. With a
`batch_axis` (the mesh's DP axis), the batch dim shards over it and
parameter gradients are additionally psum-reduced over the replicas —
the composition the mesh trainer (`ShardedJitStep`) relies on.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import stats as stats_mod

SCHEDULES = ("gpipe", "1f1b")


def _stage_params_spec(params, axis_name):
    """Every stacked param leaf shards its leading (stage) dim."""
    return jax.tree_util.tree_map(
        lambda _: P(axis_name), params,
        is_leaf=lambda x: hasattr(x, "shape"))


def _split_microbatches(x, m: int, pad: bool):
    """Validate/pad `x`'s batch dim for an m-way microbatch split with
    `data.microbatches`' pad-aware semantics (ISSUE 10 satellite): an
    indivisible batch raises the splitter's loud ValueError naming the
    sizes instead of a bare assert; `pad=True` repeat-pads the tail
    (opt-in, the accum-path contract). Returns (x, real_b) — real_b <
    x.shape[0] means the caller slices the pad rows back off the
    output.

    The actual [m, B/m, ...] reshape happens INSIDE the shard_map
    per-chip body as a pure reshape. Deliberately NOT a slice-and-
    stack (`data.microbatches`' container form): this jax version's
    SPMD partitioner mis-reshards slice-assembled values entering a
    `check_rep=False` manual region (each shard arrives scaled by the
    group size — a silent ×P corruption), while plain reshapes round-
    trip cleanly. The divisibility/pad CONTRACT is shared with
    `data.microbatches`; only the assembly differs."""
    from .. import data as data_mod

    b = int(x.shape[0])
    if b % m:
        if not pad:
            try:
                # the splitter's loud contract, re-raised with the
                # pipeline's own shape context
                data_mod.microbatches(jnp.zeros((b, 1)), m)
            except ValueError as e:
                raise ValueError(
                    f"pipeline_apply: batch shape {tuple(x.shape)} "
                    f"does not split into microbatches={m}: {e}"
                ) from None
        b2 = ((b + m - 1) // m) * m
        reps = [b2 - b] + [1] * (x.ndim - 1)
        x = jnp.concatenate([x, jnp.tile(x[-1:], reps)])
    return x, b


def pipeline_apply(stage_fn: Callable, stacked_params, x, mesh: Mesh,
                   *, axis_name: str = "pipe",
                   microbatches: Optional[int] = None,
                   schedule: str = "gpipe",
                   batch_axis: Optional[str] = None,
                   pad: bool = False):
    """Run `y = stage_P-1(...stage_1(stage_0(x)))` as a pipeline.

    stage_fn(params_i, h) -> h'   one stage, pure; same signature for
                                  every stage (homogeneous pipeline,
                                  output shape == input shape).
    stacked_params: pytree whose leaves have leading dim P (= mesh
        size along `axis_name`); leaf i on chip i.
    x: [B, ...] global batch, split into `microbatches` equal
        microbatches (default: the pipe size; the process knob
        `stats.pipeline_microbatches` — the autotuner's axis —
        overrides both). Indivisible batches raise the
        `data.microbatches` ValueError; `pad=True` repeat-pads the
        tail and slices it back off the output.
    schedule: "gpipe" (plain reverse-mode through the forward scan —
        all-M activation stash) or "1f1b" (custom-vjp combined
        schedule — in-flight activations bounded by pipe depth).
    batch_axis: mesh DP axis to shard the batch dim over (None =
        replicated). Parameter gradients psum over it.

    Returns y with x's shape (the last stage's outputs, re-assembled,
    replicated along `axis_name`). Differentiable via jax.vjp/grad.
    """
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown pipeline schedule {schedule!r}; known: "
            f"{list(SCHEDULES)}")
    pipe = mesh.shape[axis_name]
    m = stats_mod.pipeline_microbatches() or microbatches or pipe
    m = int(m)
    dp = (mesh.shape[batch_axis]
          if batch_axis and batch_axis in mesh.shape else 1)
    if batch_axis is not None and dp > 1:
        # per-replica split: each DP shard scans m microbatches of its
        # LOCAL batch, so the global batch must divide by dp * m
        if int(x.shape[0]) % dp:
            raise ValueError(
                f"pipeline_apply: batch {int(x.shape[0])} does not "
                f"shard over batch_axis {batch_axis!r} (size {dp})")
    else:
        batch_axis = None
    # validate/pad for the (per-replica) m-way split: the shard_map
    # splits dim 0 over dp, each shard pure-reshapes to its m local
    # microbatches
    x, real_b = _split_microbatches(x, m * dp, pad)
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != pipe:
            raise ValueError(
                f"pipeline_apply: stacked param leading dim "
                f"{leaf.shape[0]} != pipe size {pipe} (one stage per "
                "chip; fold extra stages into stage_fn)")
    stats_mod.note_pipeline_build(pipe, m, schedule)
    if schedule == "1f1b":
        fn = _build_1f1b(stage_fn, mesh, axis_name, m,
                         batch_axis=batch_axis)
        y = fn(stacked_params, x)
    else:
        y = _gpipe_apply(stage_fn, stacked_params, x, mesh, axis_name,
                         m, batch_axis)
    # Pin the output layout at the manual-region boundary: without
    # this, the SPMD partitioner sometimes propagates a spurious
    # sharding out of the check-rep-off shard_map into downstream
    # consumers (observed: a donated param's output shard acquiring a
    # batch-axis split, which explodes the donation alias check).
    y = lax.with_sharding_constraint(
        y, NamedSharding(mesh, P(*((batch_axis,)
                                   + (None,) * (y.ndim - 1)))))
    if real_b != int(y.shape[0]):
        y = y[:real_b]
    return y


def _forward_per_chip(stage_fn, axis_name, pipe, m):
    """The forward-only per-chip schedule (M + P - 1 ticks): the GPipe
    forward, and the primal pass of the 1F1B custom vjp. xloc is this
    chip's LOCAL batch ([dp-shard] when batch_axis is set)."""

    def per_chip(params, xloc):
        my = lax.axis_index(axis_name)
        p_local = jax.tree_util.tree_map(lambda a: a[0], params)
        mb = xloc.shape[0] // m
        xm = xloc.reshape((m, mb) + xloc.shape[1:])
        h0 = jnp.zeros((mb,) + xloc.shape[1:], xloc.dtype)
        out0 = jnp.zeros_like(xm)

        def tick(carry, t):
            h, out = carry
            feed = xm[jnp.clip(t, 0, m - 1)]
            h_in = jnp.where(my == 0, feed, h)
            h_out = stage_fn(p_local, h_in)
            done_idx = t - (pipe - 1)
            is_done = (my == pipe - 1) & (done_idx >= 0) & (done_idx < m)
            out = jnp.where(
                is_done,
                out.at[jnp.clip(done_idx, 0, m - 1)].set(h_out),
                out)
            nxt = lax.ppermute(
                h_out, axis_name,
                [(i, (i + 1) % pipe) for i in range(pipe)])
            return (nxt, out), None

        (h, out), _ = lax.scan(tick, (h0, out0),
                               jnp.arange(m + pipe - 1))
        # only the last chip's `out` is real; broadcast it to everyone
        # so the result is replicated along pipe.
        out = lax.psum(
            jnp.where(my == pipe - 1, out, jnp.zeros_like(out)),
            axis_name)
        return out.reshape(xloc.shape)

    return per_chip


def _pipe_specs(stacked_params, axis_name, batch_axis):
    pspec = _stage_params_spec(stacked_params, axis_name)
    xspec = P(batch_axis) if batch_axis else P()
    return pspec, xspec


def _gpipe_apply(stage_fn, stacked_params, x, mesh, axis_name, m,
                 batch_axis):
    pipe = mesh.shape[axis_name]
    pspec, xspec = _pipe_specs(stacked_params, axis_name, batch_axis)
    stats_mod.note_collective(axis_name, "ppermute", m + pipe - 1)
    stats_mod.note_collective(axis_name, "psum", 1)
    fn = shard_map(
        _forward_per_chip(stage_fn, axis_name, pipe, m), mesh=mesh,
        in_specs=(pspec, xspec),
        out_specs=xspec,
        check_vma=False,
    )
    return fn(stacked_params, x)


def _build_1f1b(stage_fn, mesh, axis_name, m, batch_axis=None):
    """The 1F1B schedule as a `jax.custom_vjp` closure.

    Primal/fwd: the forward-only pipeline scan; residuals are ONLY
    (params, x) — no per-tick activation stash crosses the fwd→bwd
    boundary. bwd: one combined scan of T = 2(M + P - 1) ticks; each
    tick every stage does at most one forward (saving the stage input
    into a P-slot ring buffer) and at most one backward (recomputing
    its stage via `jax.vjp` from the saved input — the in-flight
    window IS the ring buffer, so liveness is bounded by P).
    Parameter-gradient partials accumulate in fp32 per stage and come
    back stage-stacked; with a `batch_axis` they are additionally
    psum-reduced over the DP replicas (each replica backpropagates its
    own batch shard)."""
    pipe = mesh.shape[axis_name]
    T = 2 * (m + pipe - 1)

    def fwd_only(params, x):
        pspec, xspec = _pipe_specs(params, axis_name, batch_axis)
        fn = shard_map(
            _forward_per_chip(stage_fn, axis_name, pipe, m),
            mesh=mesh, in_specs=(pspec, xspec), out_specs=xspec,
            check_vma=False)
        return fn(params, x)

    def bwd_combined(params, x, gy):
        pspec, xspec = _pipe_specs(params, axis_name, batch_axis)

        def per_chip(params_l, xloc, gyloc):
            my = lax.axis_index(axis_name)
            p_local = jax.tree_util.tree_map(lambda a: a[0], params_l)
            mb = xloc.shape[0] // m
            xm = xloc.reshape((m, mb) + xloc.shape[1:])
            gym = gyloc.reshape((m, mb) + gyloc.shape[1:])
            ring0 = jnp.zeros((pipe, mb) + xloc.shape[1:], xloc.dtype)
            gacc0 = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape[1:], jnp.float32), params_l)
            dx0 = jnp.zeros_like(xm)
            h0 = jnp.zeros((mb,) + xloc.shape[1:], xloc.dtype)
            g0 = jnp.zeros((mb,) + xloc.shape[1:], xloc.dtype)

            def tick(carry, t):
                h_prev, g_next, ring, gacc, dx = carry
                # ---- forward half: microbatch kf enters stage `my`
                # at tick 2*kf + my
                kf2 = t - my
                kf = kf2 // 2
                fwd_tick = (kf2 % 2 == 0) & (kf >= 0) & (kf < m)
                kf_c = jnp.clip(kf, 0, m - 1)
                h_in = jnp.where(my == 0, xm[kf_c], h_prev)
                ring = jnp.where(fwd_tick,
                                 ring.at[kf_c % pipe].set(h_in), ring)
                h_out = stage_fn(p_local, h_in)
                # ---- backward half: microbatch kb's backward reaches
                # stage `my` at tick 2*kb + 2P - 1 - my
                kb2 = t - 2 * pipe + 1 + my
                kb = kb2 // 2
                bwd_tick = (kb2 % 2 == 0) & (kb >= 0) & (kb < m)
                kb_c = jnp.clip(kb, 0, m - 1)
                g_in = jnp.where(my == pipe - 1, gym[kb_c], g_next)
                h_saved = ring[kb_c % pipe]
                _, vjp_fn = jax.vjp(stage_fn, p_local, h_saved)
                dp, dh = vjp_fn(g_in)
                gacc = jax.tree_util.tree_map(
                    lambda a, d: a + jnp.where(
                        bwd_tick, d, jnp.zeros_like(d)
                    ).astype(jnp.float32),
                    gacc, dp)
                dx = jnp.where(bwd_tick & (my == 0),
                               dx.at[kb_c].set(dh), dx)
                # hand the activation downstream, the gradient upstream
                h_nxt = lax.ppermute(
                    jnp.where(fwd_tick, h_out, jnp.zeros_like(h_out)),
                    axis_name,
                    [(i, (i + 1) % pipe) for i in range(pipe)])
                g_prv = lax.ppermute(
                    jnp.where(bwd_tick, dh, jnp.zeros_like(dh)),
                    axis_name,
                    [(i, (i - 1) % pipe) for i in range(pipe)])
                return (h_nxt, g_prv, ring, gacc, dx), None

            (h, g, ring, gacc, dx), _ = lax.scan(
                tick, (h0, g0, ring0, gacc0, dx0), jnp.arange(T))
            if batch_axis:
                # params are replicated over the DP axis; each replica
                # accumulated grads from its own batch shard — sum them
                gacc = jax.tree_util.tree_map(
                    lambda a: lax.psum(a, batch_axis), gacc)
            gacc = jax.tree_util.tree_map(
                lambda a, pl: a[None].astype(pl.dtype), gacc, params_l)
            # dx is real only at stage 0; broadcast along pipe
            dx = lax.psum(
                jnp.where(my == 0, dx, jnp.zeros_like(dx)), axis_name)
            return gacc, dx.reshape(xloc.shape)

        stats_mod.note_collective(axis_name, "ppermute",
                                  (m + pipe - 1) + 2 * T)
        stats_mod.note_collective(axis_name, "psum", 2)
        if batch_axis:
            stats_mod.note_collective(batch_axis, "psum", 1)
        fn = shard_map(
            per_chip, mesh=mesh,
            in_specs=(pspec, xspec, xspec),
            out_specs=(pspec, xspec),
            check_vma=False)
        return fn(params, x, gy)

    @jax.custom_vjp
    def pipe_fn(params, x):
        return fwd_only(params, x)

    def fwd(params, x):
        return fwd_only(params, x), (params, x)

    def bwd(res, gy):
        params, x = res
        return bwd_combined(params, x, gy)

    pipe_fn.defvjp(fwd, bwd)
    return pipe_fn


def stack_stage_params(per_stage_params):
    """[{leaf: (shape)}, ...] x P  ->  {leaf: (P, *shape)}."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def place_stacked(stacked_params, mesh, axis_name: str = "pipe"):
    """Lay the stacked params out so chip i holds stage i."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(
            a, NamedSharding(mesh, P(axis_name))),
        stacked_params)
