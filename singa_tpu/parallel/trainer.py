"""Mesh-mode training step: the whole `train_one_batch` as one SPMD
program over a named device mesh.

This is the TPU-native successor to the reference's distributed step
(SURVEY.md §3.3): where `opt.DistOpt` drives one NCCL allreduce per
gradient from Python, here the *same user code* traces into a single
jit whose inputs carry `NamedSharding`s — GSPMD partitions the compute
and inserts the gradient reductions over ICI, and XLA's latency-hiding
scheduler overlaps them with the backward pass (the hand-tuned c1/c2
stream trick in src/io/communicator.cc, done by the compiler).

Composes DP ("data" axis: batch dim), TP ("model" axis: param rules),
and SP ("seq" axis: ring attention ops inside the model) in one step.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..model import _JitStep, _merge_accum_out
from .sharding import ShardingRules, batch_sharding, replicated


class ShardedJitStep(_JitStep):
    """`_JitStep` with mesh shardings on every program input/output.

    Params/optimizer slots are laid out per `rules` and *re-placed*
    (jax.device_put) onto the mesh at construction, so step 1 already
    runs fully sharded; batch arrays are sharded on dim 0 over "data"
    (override per-input with `batch_specs`, e.g. to also shard the
    sequence dim over "seq" for ring attention).
    """

    def __init__(self, model, mesh, rules: Optional[ShardingRules] = None,
                 batch_axis: str = "data",
                 batch_specs: Optional[Sequence] = None,
                 seq_axis: Optional[str] = None, seq_dim: int = 1,
                 plan=None):
        super().__init__(model)
        self.mesh = mesh
        self.plan = plan  # ParallelPlan (ISSUE 10); keys the AOT store
        self.rules = rules or ShardingRules()
        self.batch_axis = batch_axis
        self.batch_specs = batch_specs
        self.seq_axis = seq_axis
        self.seq_dim = seq_dim
        self._param_names = {
            id(t): n for n, t in model.get_params().items()
        }
        # Multi-controller: the mesh spans devices of other processes
        # (launch topologies train_multiprocess.py / train_mpi.py).
        self._multiproc = any(
            d.process_index != jax.process_index()
            for d in np.asarray(mesh.devices).flat)
        self._ensure_opt_slots()
        self._place()

    def _gput(self, v, sh):
        """device_put that works across controllers: a single-device
        committed array cannot be copied onto non-addressable devices,
        so bridge through the host value (every controller holds the
        same value by construction — same seed, same updates)."""
        if getattr(v, "sharding", None) == sh:
            return v
        if self._multiproc and getattr(v, "is_fully_addressable", True):
            v = np.asarray(v)
        return jax.device_put(v, sh)

    # -- sharding tables ---------------------------------------------------
    def _param_shardings(self) -> List:
        out = []
        for p in self.params:
            name = self._param_names.get(id(p), "")
            out.append(self.rules.sharding_for(self.mesh, name,
                                               p.data.shape))
        return out

    def _state_shardings(self) -> List:
        return [replicated(self.mesh) for _ in self.states]

    def _opt_shardings(self) -> List:
        """Optimizer slots inherit their param's layout (slot arrays
        are elementwise companions of the param). The step-guard state
        scalars riding the opt-state slot (`_JitStep._opt_arrays`) are
        replicated — every rank holds the same scale/counters, which
        is exactly the ranks-never-diverge contract."""
        out = []
        if self.opt is not None:
            by_id = {id(p): s for p, s in zip(self.params,
                                              self._param_shardings())}
            for pid, pstate in self.opt.states.items():
                sh = by_id.get(pid, replicated(self.mesh))
                out.extend(sh for _ in sorted(pstate))
        out.extend(replicated(self.mesh)
                   for _ in range(getattr(self, "_guard_n", 0)))
        return out

    def _batch_shardings(self, batch_arrays) -> tuple:
        if self.batch_specs is not None:
            from jax.sharding import NamedSharding

            return tuple(
                NamedSharding(self.mesh, spec)
                for spec in self.batch_specs
            )
        return tuple(
            batch_sharding(self.mesh, getattr(b, "ndim", 0),
                           batch_axis=self.batch_axis,
                           seq_axis=self.seq_axis, seq_dim=self.seq_dim)
            for b in batch_arrays
        )

    # -- placement ---------------------------------------------------------
    def _place(self):
        """Lay existing (single-device) param/state/opt arrays out on
        the mesh so the first compiled step starts sharded."""
        for p, sh in zip(self.params, self._param_shardings()):
            p.data = self._gput(p.data, sh)
        rep = replicated(self.mesh)
        for s in self.states:
            s.data = self._gput(s.data, rep)
        if self.opt is not None:
            arrays = self._opt_arrays()
            shs = self._opt_shardings()
            self._bind_opt_arrays(
                [self._gput(a, sh) for a, sh in zip(arrays, shs)]
            )

    def _prepare_inputs(self, pvals, svals, ovals, key, batch_arrays):
        """device_put everything to its mesh layout (no-op for arrays
        already placed — users may rebind p.data to host arrays).
        The step's `step.place` phase (`_JitStep.__call__`): time here
        means something upstream keeps handing the step host/off-mesh
        arrays every step."""
        rep = replicated(self.mesh)
        pvals = [self._gput(v, s)
                 for v, s in zip(pvals, self._param_shardings())]
        svals = [self._gput(v, rep) for v in svals]
        ovals = [self._gput(v, s)
                 for v, s in zip(ovals, self._opt_shardings())]
        key = self._gput(key, rep)
        batch_arrays = tuple(
            self._gput(b, s)
            for b, s in zip(batch_arrays,
                            self._batch_shardings(batch_arrays))
        )
        return pvals, svals, ovals, key, batch_arrays

    def _restore_key(self, new_key, dev):
        if not getattr(new_key, "is_fully_addressable", True):
            # Replicated over a multi-controller mesh: every process
            # holds the full value in its local shard; pull that.
            new_key = new_key.addressable_shards[0].data
        return jax.device_put(new_key, dev.jax_device)

    # -- gradient accumulation (ISSUE 4) -----------------------------------
    def _place_microbatches(self, micro):
        """GSPMD fallback layout for the scan-fused accumulation: the
        [n, mb, ...] stack keeps the scan axis replicated and the
        microbatch dims on their normal batch sharding, so each scan
        iteration computes on the same data-parallel layout a
        monolithic step would."""
        if self.batch_specs is not None:
            specs = list(self.batch_specs)
        else:
            specs = [
                batch_sharding(self.mesh, m.ndim - 1,
                               batch_axis=self.batch_axis,
                               seq_axis=self.seq_axis,
                               seq_dim=self.seq_dim).spec
                for m in micro
            ]
        return [
            jax.lax.with_sharding_constraint(
                m, NamedSharding(self.mesh, P(None, *spec)))
            for m, spec in zip(micro, specs)
        ]

    def _accum_pure_dp(self, n, batch) -> bool:
        """The single-reduction shard_map path applies when the step
        is PURE data parallelism: params/states replicated, default
        dim-0 batch sharding, no sequence axis, single controller, and
        the per-device batch divides into n microbatches. Anything
        else falls back to the GSPMD scan (correct, but the gradient
        reduction stays inside the loop)."""
        if self.batch_specs is not None or self.seq_axis is not None:
            return False
        if self._multiproc:
            return False
        if self.batch_axis not in self.mesh.shape:
            return False
        ndev = self.mesh.shape[self.batch_axis]
        for b in batch:
            if getattr(b, "ndim", 0) < 1 or b.shape[0] % (n * ndev):
                return False
        return all(s.spec == P() for s in self._param_shardings())

    def _accum_step(self, n, pvals, svals, ovals, key, step_counter,
                    batch):
        """Mesh-mode accumulation. Pure-DP steps take the
        single-reduction path: the step runs under `shard_map`, each
        device scans its LOCAL batch shard as n microbatches
        (accumulating local fp32 gradient partials — zero collectives
        inside the loop), and the cross-device reduction is ONE
        variadic `psum` of a flat fp32 bucket carrying every gradient,
        the loss sum, and the float layer states — so an n-accum step
        issues exactly one all-reduce, after the scan, where the
        monolithic step issued one per batch and a Python accumulation
        loop would issue n. The optimizer then applies on the global
        mean inside the same program (identical on every device; the
        StepGuard finite bit is computed from the post-psum global
        grads, so ranks can never diverge).

        Semantics notes vs the monolithic mesh step (classic
        data-parallel semantics, documented in README): batch-coupled
        statistics (BN) are computed per device shard and
        psum-averaged into the running stats, and the microbatch
        partition is per-device-local rather than global-contiguous.
        Gradient math is unchanged — the accumulated mean equals the
        monolithic gradient up to fp32 summation order.

        Non-pure-DP configurations (TP rules, seq sharding,
        multi-controller, indivisible local batches) fall back to the
        GSPMD scan of the base class: same math, but GSPMD keeps the
        gradient all-reduce inside the scan body (n reductions per
        step — on real TPUs XLA's while-loop all-reduce code motion
        can still hoist it)."""
        import jax.numpy as jnp

        from ..model import _bound_model
        from jax import shard_map

        if not self._accum_pure_dp(n, batch):
            return super()._accum_step(n, pvals, svals, ovals, key,
                                       step_counter, batch)
        mesh, ax = self.mesh, self.batch_axis
        ndev = mesh.shape[ax]
        dev = self._device()
        model, opt = self.model, self.opt
        params, states = self.params, self.states
        mbl = batch[0].shape[0] // (n * ndev)
        mb_specs = [
            jax.ShapeDtypeStruct(
                (b.shape[0] // (n * ndev),) + tuple(b.shape[1:]),
                b.dtype)
            for b in batch
        ]
        # Discovery runs at the outer level with LOCAL microbatch
        # shapes: grad order + the per-microbatch out tree (which
        # fixes the shard_map out_specs before any tracing).
        saved_o = self._opt_arrays()
        with _bound_model(params, states, dev, pvals, svals, key):
            try:
                self._bind_opt_arrays(ovals)
                order, outs_sds = self._discover_accum_order(
                    dev, svals, key, mb_specs)
            finally:
                self._bind_opt_arrays(saved_o)

        def is_batch_leaf(sds):
            return (getattr(sds, "ndim", 0) >= 1
                    and sds.shape[0] == mbl)

        # Non-batch INTEGER output leaves cannot ride this path
        # honestly: the psum bucket only reduces float leaves (their
        # mean semantics are well-defined), and presenting a
        # device-local integer metric as global would silently report
        # one shard's value. Such models take the GSPMD fallback,
        # which computes every output leaf globally.
        import jax.numpy as _jnp

        for sds in jax.tree_util.tree_leaves(outs_sds):
            if (not is_batch_leaf(sds)
                    and not _jnp.issubdtype(sds.dtype, _jnp.inexact)):
                return super()._accum_step(n, pvals, svals, ovals,
                                           key, step_counter, batch)

        outs_specs = jax.tree_util.tree_map(
            lambda sds: P(ax) if is_batch_leaf(sds) else P(),
            outs_sds)

        def local_fn(pvals_l, svals_l, ovals_l, key_l, step_l,
                     *batch_l):
            saved_o = self._opt_arrays()
            saved_step = opt.step_counter
            with _bound_model(params, states, dev, pvals_l, svals_l,
                              key_l):
                try:
                    self._bind_opt_arrays(list(ovals_l))
                    opt.step_counter = step_l
                    micro = [
                        b.reshape((n, b.shape[0] // n)
                                  + tuple(b.shape[1:]))
                        for b in batch_l
                    ]
                    # Per-device RNG decorrelation (classic DDP
                    # semantics): the replicated key would give every
                    # device's shard the SAME dropout/noise masks —
                    # fold the data-axis index in so each replica
                    # draws an independent stream. The returned global
                    # key advances by fold_in(key, n) — replicated,
                    # deterministic, independent of how many splits
                    # the model consumed.
                    local_key = jax.random.fold_in(
                        key_l, jax.lax.axis_index(ax))
                    (svals_f, key_f, acc, loss_sum), outs = \
                        self._accum_scan(dev, order, svals_l,
                                         local_key, micro)
                    for s, v in zip(states, svals_f):
                        s.data = v
                    dev._rng_key = jax.random.fold_in(
                        key_l, np.int32(n))
                    merged = _merge_accum_out(outs, mbl)
                    # ---- the ONE reduction: a flat fp32 bucket of
                    # every gradient partial + the loss sum + the
                    # float layer states + non-batch float outputs,
                    # psum'd in a single variadic all-reduce (the
                    # fused-bucket idiom of DistOpt.fused_synch).
                    fstate_ix = [
                        i for i, s in enumerate(states)
                        if jnp.issubdtype(jnp.asarray(s.data).dtype,
                                          jnp.inexact)
                    ]
                    mleaves, mtree = jax.tree_util.tree_flatten(
                        merged)
                    fout_ix = [
                        i for i, a in enumerate(mleaves)
                        if jnp.issubdtype(jnp.asarray(a).dtype,
                                          jnp.inexact)
                        and not (getattr(a, "ndim", 0) >= 1
                                 and a.shape[0] == n * mbl)
                    ]
                    with jax.named_scope("opt/accum"):
                        parts = ([a.reshape(-1) for a in acc]
                                 + [loss_sum.reshape(1)]
                                 + [jnp.asarray(states[i].data)
                                    .astype(jnp.float32).reshape(-1)
                                    for i in fstate_ix]
                                 + [jnp.asarray(mleaves[i])
                                    .astype(jnp.float32).reshape(-1)
                                    for i in fout_ix])
                        sizes = [int(np.prod(p.shape)) for p in parts]
                        flat = (jnp.concatenate(parts)
                                if len(parts) > 1 else parts[0])
                        red = jax.lax.psum(flat, ax)
                        pieces, off = [], 0
                        for sz in sizes:
                            pieces.append(red[off:off + sz])
                            off += sz
                        k = len(acc)
                        acc = [pc.reshape(p.data.shape)
                               for pc, p in zip(pieces[:k], order)]
                        loss_sum = pieces[k].reshape(())
                        k += 1
                        for j, i in enumerate(fstate_ix):
                            orig = states[i].data
                            states[i].data = (
                                (pieces[k + j] / ndev)
                                .astype(orig.dtype).reshape(orig.shape))
                        k += len(fstate_ix)
                        for j, i in enumerate(fout_ix):
                            orig = mleaves[i]
                            mleaves[i] = (
                                (pieces[k + j] / ndev)
                                .astype(orig.dtype).reshape(orig.shape))
                        merged = jax.tree_util.tree_unflatten(mtree,
                                                              mleaves)
                    # one apply on the global mean (n * ndev
                    # microbatches contributed to the sums)
                    opt.apply_accumulated(
                        loss_sum, list(zip(order, acc)), n * ndev)
                    new_p = [p.data for p in params]
                    new_s = [s.data for s in states]
                    new_o = self._opt_arrays()
                    new_key = dev._rng_key
                    return merged, new_p, new_s, new_o, new_key
                finally:
                    self._bind_opt_arrays(saved_o)
                    opt.step_counter = saved_step

        fn = shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P())
            + tuple(P(ax) for _ in batch),
            out_specs=(outs_specs, P(), P(), P(), P()),
            check_vma=False)
        return fn(pvals, svals, ovals, key, step_counter, *batch)

    # -- AOT export cache (ISSUE 6) ----------------------------------------
    def _export_kind(self) -> str:
        return "sharded_step"

    def _export_extras(self):
        """Mesh identity for the artifact key: an exported SPMD
        program is specialized to its mesh layout, so axis names/
        sizes, the sharding rules, batch-spec overrides, and the
        controller topology all invalidate on change."""
        from .. import export_cache

        return {
            "mesh_axes": {str(k): int(v)
                          for k, v in self.mesh.shape.items()},
            "batch_axis": self.batch_axis,
            "seq": [self.seq_axis, self.seq_dim],
            "batch_specs": (None if self.batch_specs is None
                            else [repr(s) for s in self.batch_specs]),
            "rules": export_cache._scalarize(self.rules),
            "multiproc": bool(self._multiproc),
            # ParallelPlan identity (ISSUE 10): schedule/microbatch/
            # capacity policy bakes a different traced program even on
            # an identical mesh — a plan flip must orphan artifacts
            # (and flipping back re-hits).
            "plan": (None if self.plan is None
                     else self.plan.fingerprint()),
        }

    # -- jit wiring --------------------------------------------------------
    def _build(self, *batch_arrays, donate=None):
        """Pipeline/expert meshes build with donation OFF (ISSUE 10):
        this jax version's SPMD partitioner can propagate a spurious
        batch-axis sharding out of the 1F1B schedule's check-rep-off
        manual region (and, shape-dependent, out of the MoE dispatch's
        expert sharding constraints) into an unrelated donated param's
        OUTPUT, and the donation alias check then explodes at dispatch
        ("aliased input/output to have the same size"). The pure
        DP/TP/SP axes keep the aliasing contract; pipe/expert trade it
        for correctness — the same conservative discipline as
        export-cached steps."""
        if donate is None and (self.mesh.shape.get("pipe", 1) > 1
                               or self.mesh.shape.get("expert", 1) > 1):
            donate = False
        return super()._build(*batch_arrays, donate=donate)

    def _jit_kwargs(self, batch_arrays):
        rep = replicated(self.mesh)
        p_sh = self._param_shardings()
        s_sh = self._state_shardings()
        o_sh = self._opt_shardings()
        in_shardings = (p_sh, s_sh, o_sh, rep, rep,
                        self._batch_shardings(batch_arrays))
        # Outputs: (out_arrays, new_p, new_s, new_o, new_key) — model
        # outputs unconstrained (None = compiler chooses), round-trip
        # state pinned to its input layout so donation aliases cleanly.
        out_shardings = (None, p_sh, s_sh, o_sh, rep)
        return {"in_shardings": in_shardings,
                "out_shardings": out_shardings}
