"""Optimizers + distributed optimizer driver.

Reference parity: `python/singa/opt.py` — `Optimizer` base with
`DecayScheduler`s, `SGD` (momentum/nesterov/weight_decay/dampening),
`RMSProp`, `AdaGrad`, `Adam`, and `DistOpt` (the data-parallel driver
over the NCCL Communicator, here over `singa_tpu.dist.Communicator`
— XLA collectives on the device mesh).

Update math is written as pure jnp expressions over `param.data`, so
the same optimizer code runs eagerly per-op AND traces into the
whole-step `jax.jit` program built by `Model.compile(use_graph=True)`
(state dicts rebind like param tensors do).
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd, resilience, stats as stats_mod, \
    tensor as tensor_mod, trace as trace_mod
from .tensor import Tensor

# _DONATION_FILTER: donated-but-unaliased buffers are deliberate
# throughout this module (grads outnumber outputs — donation still
# frees them early) and also arise on replay when a caller rebinds
# host-numpy params (post-restore: numpy inputs cannot be donated).
# Installed ONCE at import: a per-call warnings.catch_warnings() on
# the fused hot path would copy/restore the process-global filter
# list every step and race other threads.
import warnings as _warnings

_warnings.filterwarnings(
    "ignore", message=".*[Ss]ome donated buffers were not usable.*")

# Shared counters over every optimizer instance's fused-update cache
# (the caches themselves are per-instance; the observability question
# — "is the process retracing optimizer updates every step?" — is
# process-global). Snapshot via singa_tpu.stats.cache_stats().
_FUSED_STATS = stats_mod.CacheStats("fused_opt")
stats_mod.register_cache("fused_opt", _FUSED_STATS)


import functools as _functools


@_functools.lru_cache(maxsize=64)
def _accum_finish_exec(n_total: int, dtypes: tuple):
    """Jitted accumulation finisher for the eager path: mean + cast
    for every accumulated gradient in ONE dispatch (accumulator
    buffers donated). Cached per (n, dtype-tuple); jax re-caches per
    shape set inside. Must stay expression-identical to the traced
    inline branch in `Optimizer.apply_accumulated`."""
    nf = jnp.float32(n_total)

    def fin(acc, loss_sum):
        return ([(a / nf).astype(dt) for a, dt in zip(acc, dtypes)],
                jnp.asarray(loss_sum).astype(jnp.float32) / nf)

    return jax.jit(fin, donate_argnums=(0,))


class DecayScheduler:
    """Reference: `opt.DecayScheduler`. Maps step → learning rate."""

    def __init__(self, init_value: float):
        self.init_value = init_value

    def __call__(self, step: int):
        raise NotImplementedError


class Constant(DecayScheduler):
    def __call__(self, step: int):
        return self.init_value


class ExponentialDecay(DecayScheduler):
    """Reference: `opt.ExponentialDecay(init, decay_steps, rate, staircase)`."""

    def __init__(self, init_value, decay_steps, decay_rate, staircase=False):
        super().__init__(init_value)
        self.decay_steps = decay_steps
        self.decay_rate = decay_rate
        self.staircase = staircase

    def __call__(self, step: int):
        p = step / self.decay_steps
        if self.staircase:
            p = jnp.floor(p) if not isinstance(step, int) else int(p)
        return self.init_value * (self.decay_rate ** p)


class CosineDecay(DecayScheduler):
    """Cosine annealing from `init_value` to `final_value` over
    `decay_steps`, flat afterwards. No reference equivalent (the
    reference ships Constant/ExponentialDecay only); standard for the
    transformer workloads this framework adds. jit-safe: works with
    traced step values."""

    def __init__(self, init_value, decay_steps, final_value=0.0):
        super().__init__(init_value)
        self.decay_steps = decay_steps
        self.final_value = final_value

    def __call__(self, step):
        p = jnp.clip(step / self.decay_steps, 0.0, 1.0)
        cos = 0.5 * (1.0 + jnp.cos(jnp.pi * p))
        return self.final_value + (self.init_value
                                   - self.final_value) * cos


class WarmupWrapper(DecayScheduler):
    """Linear warmup from 0 to the inner scheduler's value over
    `warmup_steps`, then defers to `inner(step - warmup_steps)`.
    Composes with any `DecayScheduler`."""

    def __init__(self, inner: "DecayScheduler", warmup_steps: int):
        super().__init__(inner.init_value)
        self.inner = inner
        self.warmup_steps = warmup_steps

    def __call__(self, step):
        w = self.warmup_steps
        warm = self.init_value * (step + 1) / max(1, w)
        after = self.inner(jnp.maximum(0, step - w)
                           if not isinstance(step, int)
                           else max(0, step - w))
        if isinstance(step, int):
            return warm if step < w else after
        return jnp.where(step < w, warm, after)


def _global_clip_scale(clip_norm, grads):
    """min(1, clip/||g||) over raw grad arrays, norm in fp32."""
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in grads)
    return jnp.minimum(1.0, clip_norm / (jnp.sqrt(sq) + 1e-12))


class Optimizer:
    """Reference: `opt.Optimizer`. Holds step counter + per-param state.

    Per-param state is a dict name→array so it can be captured by the
    jit-ed train step (graph mode) and checkpointed alongside params.
    """

    # Slot names whose math degrades disproportionately in low
    # precision (subclasses override): set_slot_dtype excludes them by
    # default, so e.g. AdaGrad's monotone `history` accumulator — bf16
    # addition of small squares stalls at 8 mantissa bits — stays in
    # the master dtype unless the caller opts it in explicitly.
    _fragile_slots: tuple = ()

    def __init__(self, lr):
        self.lr = lr if isinstance(lr, DecayScheduler) else Constant(lr)
        self.step_counter = 0
        # id(param) -> {"slot_name": array}; insertion-ordered.
        self.states: Dict[int, Dict[str, jnp.ndarray]] = {}
        # Low-precision optimizer-state policy (byte diet, ISSUE 2):
        # None = slots stored in the param dtype (the fp32 default).
        # "bfloat16"/"float16" = slots STORED half-width — halving the
        # optimizer-state HBM round-trip per step — while the update
        # math stays in the param (master) dtype: slots are cast in
        # before `apply` and cast back out after, inside the same
        # fused/jitted program (_apply_masterized), so the only
        # precision loss is the per-step slot quantization.
        self.slot_dtype: Optional[str] = None
        self._slot_exclude: tuple = ()
        # Optional global-norm gradient clipping (no reference
        # equivalent; standard for the transformer workloads). Applies
        # in `backward_and_update` — including inside the mesh-mode
        # jitted step, where grads are already psum-reduced, so the
        # clip is by TRUE global norm. DistOpt's plain/half paths clip
        # after the allreduce (`DistOpt._clip_pairs`); the partial/
        # sparse variants bypass it (per-grad streaming by design).
        self.clip_norm: Optional[float] = None
        # Gradient-accumulation capture (ISSUE 4): while a list, each
        # backward_and_update STASHES its (loss, pairs) instead of
        # applying — the accumulation driver (Model's eager accum loop
        # or the scan-fused graph step) sums the grads in fp32 and
        # applies once via apply_accumulated.
        self._accum_capture = None
        self._accum_skip_backward = False

    def set_clip_norm(self, value: Optional[float]):
        """Clip gradients to `value` by global L2 norm (None = off)."""
        self.clip_norm = value
        return self

    def set_slot_dtype(self, dtype, exclude=None):
        """Store optimizer state (momentum/variance slots) in `dtype`
        ("bfloat16"/"float16"; None restores full precision), with
        fp32-master update math (cast-in/cast-out inside the fused
        update). `exclude` names slots that keep the master dtype; it
        defaults to the optimizer's numerically fragile slots
        (`_fragile_slots` — e.g. AdaGrad's `history`), pass `()` to
        opt everything in. Existing slots convert lazily on their next
        update. Chainable."""
        resolved = None if dtype is None else str(jnp.dtype(dtype))
        if resolved not in (None, "bfloat16", "float16"):
            # validate BEFORE mutating: a rejected dtype must leave the
            # live policy untouched for callers that catch the error
            raise ValueError(
                f"slot_dtype must be None/bfloat16/float16, got {dtype!r}")
        self.slot_dtype = resolved
        self._slot_exclude = tuple(sorted(
            self._fragile_slots if exclude is None else exclude))
        return self

    def slot_store_dtype(self, name: str, param):
        """Storage dtype for slot `name` of `param` under the current
        slot_dtype policy (the param/master dtype when the policy is
        off or the slot is excluded)."""
        pdt = (param.data if isinstance(param, Tensor) else param).dtype
        if self.slot_dtype is None or name in self._slot_exclude:
            return pdt
        return jnp.dtype(self.slot_dtype)

    def _store_slot(self, st, name, value, master):
        """Write slot `name` at its storage dtype and return the value
        the rest of the update should consume: the STORED (quantized)
        slot, upcast to master. Consuming the quantized value — not
        the pre-quantization fp32 intermediate — keeps the XLA
        dataflow single-source, so the param-update fusion reads the
        half-width slot instead of re-deriving the fp32 chain (which
        would re-read the gradient and erase the byte saving)."""
        sd = self.slot_store_dtype(name, value)
        if value.dtype != sd:
            value = value.astype(sd)
        st[name] = value
        return value.astype(master) if value.dtype != master else value

    def _apply_masterized(self, param, value, grad):
        """`apply` with master-precision slot math: cast this param's
        slots up to the master (param) dtype, run the subclass's
        update (whose `_store_slot` writes quantize back to the
        storage dtype), then sweep any remaining slots a custom
        subclass stored without `_store_slot` down to storage. A no-op
        when slot_dtype is off — and inside a traced program (fused
        eager update, graph-mode step) the casts fuse into the
        surrounding XLA program, so half-width slots halve the state
        bytes moved without a separate pass."""
        pid = id(param)
        st = self.states.get(pid)
        master = value.dtype
        if st:
            for k, a in st.items():
                if a.dtype != master:
                    st[k] = a.astype(master)
        new_value = self.apply(param, value, grad)
        st = self.states.get(pid)
        if st is not None and self.slot_dtype is not None:
            for k, a in st.items():
                sd = self.slot_store_dtype(k, param)
                if a.dtype != sd:
                    st[k] = a.astype(sd)
        return new_value

    @property
    def lr_value(self):
        return self.lr(self.step_counter)

    def update(self, param: Tensor, grad: Tensor) -> None:
        """Apply one update to `param` in place (rebinds `.data`)."""
        g = grad.data if isinstance(grad, Tensor) else grad
        traced = isinstance(param.data, jax.core.Tracer) or isinstance(
            g, jax.core.Tracer)
        with (jax.named_scope(self._update_scope(param)) if traced
              else contextlib.nullcontext()):
            if g.dtype != param.data.dtype:
                # fp16/bf16 grads (half allreduce path) apply to fp32
                # master.
                g = g.astype(param.data.dtype)
            if traced:
                # graph mode: the whole step is one traced program;
                # the plain expressions fuse there anyway
                param.data = self._apply_masterized(param, param.data, g)
            else:
                self._fused_eager_update_all([(param, g)])

    def _update_scope(self, param) -> str:
        """The `jax.named_scope` of one parameter's update inside a
        traced step program, by the name `get_params()` gave it: how a
        device operation of the update is placed
        (hlo_profile.scope_map). The glue around the updates has a
        scope of its own each: `opt/guard` (the step guard's finite
        check, select and counters), `opt/loss_scale` (the scaled seed
        and the unscale), `opt/clip` (the global-norm clip),
        `opt/accum` (accumulation's sums and mean), `opt/allreduce`
        (DistOpt's reductions)."""
        return (f"opt/{type(self).__name__}/"
                f"{getattr(param, 'name', None) or 'unnamed'}")

    def _hyper_key(self):
        """Scalar hyperparameter snapshot for the fused-update cache:
        the jitted executables bake hyperparameters in at trace time,
        so mutating one (or swapping the LR scheduler) must miss the
        cache instead of silently keeping the old math.  The step
        counter is excluded — it is threaded through as a traced
        argument and stays dynamic."""
        import numbers

        def leaf(v):
            if isinstance(v, (int, float, bool, str, type(None))):
                return v
            if isinstance(v, numbers.Number):  # np scalars etc.
                return float(v)
            if isinstance(v, (list, tuple)):
                return tuple(leaf(x) for x in v)
            if isinstance(v, np.ndarray):
                return (v.shape, str(v.dtype), v.tobytes())
            if isinstance(v, DecayScheduler):
                return snap(v)
            # unknown object: key on identity so SWAPPING it retraces
            # (in-place mutation of an opaque object is out of scope)
            return ("obj", type(v).__name__, id(v))

        def snap(obj):
            items = []
            for k, v in sorted(vars(obj).items()):
                if k in ("step_counter", "states", "_fused_cache",
                         "_fused_static", "_accum_capture"):
                    continue
                items.append((k, leaf(v)))
            return (type(obj).__name__, tuple(items))

        return snap(self)

    def _fused_eager_update_all(self, pairs, clip=False,
                                loss=None) -> None:
        """Whole-step eager optimizer fusion: every (param, grad)
        pair's update — slot math included — runs as ONE jitted
        executable, traced from the subclass's own `apply` by threading
        the state dict and step counter through as traced arguments —
        the update math stays in exactly one place, and an N-param
        model pays one dispatch instead of N.

        When the step guard is on and `loss` is provided (the
        whole-step path from `backward_and_update`), the same
        executable also: unscales grads by the live loss scale,
        computes the all-finite bit over loss + grads, SELECTS the
        pre-step param/slot values when non-finite, and advances the
        guard counters/scale — still one dispatch, no host sync."""
        prepared = []
        for p, g in pairs:
            g = g.data if isinstance(g, Tensor) else g
            if g.dtype != p.data.dtype:
                g = g.astype(p.data.dtype)
            prepared.append((p, g))
        pids_key = tuple(id(p) for p, _ in prepared)
        do_clip = clip and self.clip_norm is not None
        # The static half of the cache key (slot-name lists + per-param
        # shape/dtype tuple) is itself memoized per param set: building
        # it fresh each step (N sorted() calls + 2N str(dtype)) was
        # ~25% of eager step time. The validation tuple is cheap
        # attribute reads; it must be NAME-sensitive, not count-
        # sensitive — an optimizer whose slot set swaps one name for
        # another at equal count (a hyper toggle) must invalidate the
        # memoized names_list/stat_key, not silently fetch the wrong
        # slots. tuple(dict) (insertion-order key tuple, <=2 names)
        # costs about the same as len() did.
        val = tuple((tuple(self.states.get(pid, ())), p.data.dtype,
                     p.data.shape) for (p, _), pid in
                    zip(prepared, pids_key))
        smemo = self.__dict__.setdefault("_fused_static", {})
        static = smemo.get(pids_key)
        if static is None or static[0] != val:
            names_list = [tuple(sorted(self.states.get(pid, {})))
                          for pid in pids_key]
            stat_key = tuple(
                (pid, nm, p.data.shape, str(p.data.dtype),
                 str(g.dtype))
                for (p, g), pid, nm in zip(prepared, pids_key,
                                           names_list))
            static = (val, names_list, stat_key)
            smemo[pids_key] = static
            while len(smemo) > 4096:
                del smemo[next(iter(smemo))]
        _, names_list, stat_key = static
        values = [p.data for p, _ in prepared]
        gs = [g for _, g in prepared]
        slots = [[self.states[pid][n] for n in nm] if nm else []
                 for pid, nm in zip(pids_key, names_list)]
        # Donation requires every donated buffer to be unique AND not
        # also appear as a non-donated argument; tied weights that
        # alias one array across Tensor objects would otherwise crash
        # with a duplicate-donation error. The whole path is gated on
        # the `buffer_donation` eager-config knob
        # (device.set_buffer_donation) — part of the donate cache key,
        # so toggling retraces instead of reusing the wrong aliasing.
        flat_args = values + gs + [a for sl in slots for a in sl]
        donate = stats_mod.donation_enabled() and (
            len({id(a) for a in flat_args}) == len(flat_args))
        # Grad buffers are additionally donatable only on the
        # whole-step path (`clip=True`: the pairs are internal to
        # backward_and_update, never handed to the caller) AND when
        # every grad carries the recorded-backward provenance flag
        # (autograd._dag_pairs: fresh replay-jit outputs nothing else
        # references). A user-held grad Tensor, or a walk-path
        # cotangent that may alias the cached root ones, must never be
        # invalidated under the user.
        donate_grads = donate and clip and all(
            isinstance(g, Tensor) and getattr(g, "_donatable", False)
            for _, g in pairs)
        # Step guard rides only the whole-step path (loss provided):
        # per-param streaming calls (DistOpt update()) must not advance
        # the guard counters once per PARAM. Guard config is part of
        # the cache key — toggling retraces instead of reusing a
        # program with the old policy baked in.
        guard = loss is not None and resilience.guard_active()
        gkey = resilience.config_key() if guard else None
        key = (self._hyper_key(), donate, donate_grads, do_clip,
               stat_key, gkey)
        cache = self.__dict__.setdefault("_fused_cache", {})
        ent = cache.get(key)
        created = ent is None
        if created:
            _FUSED_STATS.misses += 1
            # Evict superseded entries for the same param set (the
            # pre-slot-creation executable from step 1 is dead weight
            # once slots exist — its closure pins the param list), and
            # bound the cache overall (an optimizer reused across
            # rebuilt models would otherwise pin dead params forever).
            # Entries that differ ONLY in the donation flags (key[1:3])
            # are siblings, not superseded: a workload alternating
            # recorded-backward and walk grads flips donate_grads per
            # step, and evicting the other variant would retrace the
            # fused update on every flip.
            for k in [k for k, (_, _, pk_) in cache.items()
                      if pk_ == pids_key and k != key
                      and not (k[0] == key[0] and k[3:] == key[3:])]:
                del cache[k]
                _FUSED_STATS.evictions_positive += 1
            # The same-pids eviction above already bounds the cache to
            # ONE entry per param set, so steady state is 1 entry for
            # batched updates or N for DistOpt's per-param streaming —
            # the global cap only guards optimizer-outlives-model
            # leaks.  It must exceed any realistic param count, or a
            # large model streamed per-param would evict its own
            # entries every step and retrace everything (FIFO thrash).
            while len(cache) >= 4096:
                del cache[next(iter(cache))]
                _FUSED_STATS.evictions_positive += 1
            params = [p for p, _ in prepared]
            pids = [id(p) for p in params]
            meta = {}

            def core(values, gs, step, slots):
                saved = {pid: self.states.get(pid) for pid in pids}
                saved_step = self.step_counter
                self.step_counter = step
                try:
                    if do_clip:
                        # global-norm clip fused into the same program
                        # (only from backward_and_update, which sees
                        # the FULL grad set; a single-pair update()
                        # must never clip by one grad's norm)
                        scale = _global_clip_scale(self.clip_norm, gs)
                        gs = [(g.astype(jnp.float32)
                               * scale).astype(g.dtype) for g in gs]
                    new_values, new_slots, out_names = [], [], []
                    for p, pid, nm, v, g, sl in zip(
                            params, pids, names_list, values, gs,
                            slots):
                        self.states[pid] = dict(zip(nm, sl))
                        new_values.append(self._apply_masterized(p, v, g))
                        st = self.states[pid]
                        onm = tuple(sorted(st))
                        out_names.append(onm)
                        new_slots.append([st[n] for n in onm])
                    meta["names"] = out_names
                    return new_values, new_slots
                finally:
                    self.step_counter = saved_step
                    for pid in pids:
                        if saved[pid] is None:
                            self.states.pop(pid, None)
                        else:
                            self.states[pid] = saved[pid]

            if guard:
                # KEEP IN LOCKSTEP with _guarded_traced_update: same
                # finite-bit definition (resilience.all_finite over
                # loss+grads), same unscale-in-apply-branch, same
                # cond-apply/skip with where-select fallback, same
                # resilience.advance_state. The POLICY math lives in
                # resilience; only the orchestration differs (cached
                # standalone executable here vs in-trace mutation
                # there).
                scfg = resilience.scaling_config()
                # Probe the update's OUT slot structure once per cache
                # entry (host-side abstract trace): in steady state
                # (slot names unchanged by `apply`) the guard is a
                # `lax.cond` — the finite bit is computed from the raw
                # grads first, then ONLY the taken branch executes, so
                # a skip costs nothing, the apply path pays just the
                # grads-read of the finite check, and param/slot
                # donation stays fully in place (an output-side
                # where-select would pin the old buffers to program
                # end and break in-place reuse — measured ~25% on the
                # fused update). Slot-CREATING entries (step 1: cond
                # branches couldn't return matching structures) take
                # the where-select fallback; that entry is superseded
                # at step 2 anyway.
                try:
                    jax.eval_shape(core, values, gs, 0, slots)
                    stable = tuple(meta["names"]) == tuple(names_list)
                except Exception:
                    stable = False

                def _advanced(finite, gstate):
                    scale, counters = gstate
                    return resilience.advance_state(finite, scale,
                                                    counters)

                def _unscale(gs, scale):
                    if scfg is None:
                        return gs
                    # finite(g) == finite(g/s) for finite s>0, so the
                    # check ran on the raw scaled grads and only the
                    # apply path pays the unscale
                    inv = 1.0 / scale
                    return [g * inv.astype(g.dtype) for g in gs]

                if stable:
                    def pure(values, gs, step, slots, gstate,
                             loss_arr):
                        scale, _ = gstate
                        finite = resilience.all_finite(
                            [loss_arr] + gs)

                        def apply_branch(op):
                            v, g, sl = op
                            return core(v, _unscale(g, scale), step,
                                        sl)

                        def skip_branch(op):
                            v, g, sl = op
                            return list(v), [list(s) for s in sl]

                        new_values, new_slots = jax.lax.cond(
                            finite, apply_branch, skip_branch,
                            (values, gs, slots))
                        return (new_values, new_slots,
                                _advanced(finite, gstate))
                else:
                    def pure(values, gs, step, slots, gstate,
                             loss_arr):
                        scale, _ = gstate
                        finite = resilience.all_finite(
                            [loss_arr] + gs)
                        new_values, new_slots = core(
                            values, _unscale(gs, scale), step, slots)
                        new_values = [jnp.where(finite, nv, v)
                                      for nv, v in zip(new_values,
                                                       values)]
                        sel = []
                        for nm_in, sl_in, onm, sl_out in zip(
                                names_list, slots, meta["names"],
                                new_slots):
                            old = dict(zip(nm_in, sl_in))
                            sel.append([
                                jnp.where(finite, a,
                                          old.get(n,
                                                  jnp.zeros_like(a)))
                                for n, a in zip(onm, sl_out)])
                        return new_values, sel, _advanced(finite,
                                                          gstate)
            else:
                pure = core
            # Donate the param/slot buffers (same contract as the
            # graph-mode _JitStep) — plus the grad buffers on the
            # flagged whole-step path: XLA updates them in place,
            # halving the update's memory traffic.  Anything holding a
            # stale reference (checkpoint snapshots fork with jnp.copy
            # first) would error loudly on use-after-donate.
            argnums = () if not donate else (
                (0, 1, 3) if donate_grads else (0, 3))
            ent = (jax.jit(pure, donate_argnums=argnums), meta,
                   pids_key)
            cache[key] = ent
        else:
            _FUSED_STATS.hits += 1
        fn, meta, _ = ent
        call_args = (values, gs, self.step_counter, slots)
        if guard:
            loss_arr = loss.data if isinstance(loss, Tensor) else loss
            call_args += (tuple(resilience.state_arrays()), loss_arr)
        if created:
            # First invocation = the trace+compile; steady-state hits
            # replay the executable (the donated-buffers lowering
            # warning is suppressed module-wide, see _DONATION_FILTER).
            t0 = time.perf_counter()
            with trace_mod.span("opt_apply"):
                out = fn(*call_args)
            _FUSED_STATS.record_trace(time.perf_counter() - t0)
        else:
            # opt_apply: the one fused optimizer dispatch of an eager
            # step (singa_tpu.trace span; null context when disabled)
            with trace_mod.span("opt_apply"):
                out = fn(*call_args)
        if guard:
            new_values, new_slots, new_gstate = out
            resilience.bind_state_arrays(new_gstate)
        else:
            new_values, new_slots = out
        for (p, _), onm, nv, ns in zip(prepared, meta["names"],
                                       new_values, new_slots):
            p.data = nv
            if onm:
                self.states[id(p)] = dict(zip(onm, ns))

    def apply(self, param: Tensor, value, grad):
        raise NotImplementedError

    def step(self) -> None:
        """Advance the LR/step schedule. Reference: `Optimizer.step`."""
        self.step_counter += 1

    def __call__(self, loss: Tensor):
        return self.backward_and_update(loss)

    # -- gradient-accumulation capture (ISSUE 4) ---------------------------
    def _accum_begin(self, skip_backward: bool = False) -> None:
        """Arm capture mode: subsequent `backward_and_update` calls
        stash their (loss, pairs) instead of applying. Used by the
        accumulation drivers (Model's eager microbatch loop and the
        scan-fused graph step); always paired with `_accum_end`.

        `skip_backward=True` (the scan-level remat path, ISSUE 9)
        stashes `(loss, None)` WITHOUT running the framework backward
        at all: the caller derives gradients itself via `jax.vjp` over
        the checkpointed forward region, so tracing the per-op walk
        here would be dead weight the compiler has to DCE."""
        self._accum_capture = []
        self._accum_skip_backward = bool(skip_backward)

    def _accum_end(self):
        """Disarm capture mode and return the captured list of
        (loss, pairs) tuples (one per backward that ran)."""
        cap, self._accum_capture = self._accum_capture, None
        self._accum_skip_backward = False
        return cap

    def apply_accumulated(self, loss_sum, acc_pairs, n_total: int):
        """Apply ONE optimizer step from fp32-accumulated gradient
        SUMS over `n_total` microbatches: mean = sum / n_total, cast
        to the param dtype, then the exact `apply_gradients` path a
        monolithic step takes — so the StepGuard finite check and the
        DynamicLossScaler unscale see the accumulated gradients once,
        global-norm clipping clips the accumulated mean, bf16 slot
        storage quantizes once, and the guard counters/scale advance
        once per accumulated step. Works eagerly (concrete arrays →
        fused update) and traced (inside the scan-fused graph step).

        Division by n_total is elementwise IEEE division (never
        reassociated by fusion), so the eager and graph accumulation
        paths produce bit-identical means for any n."""
        nf = jnp.float32(n_total)
        concrete = not (isinstance(loss_sum, jax.core.Tracer) or any(
            isinstance(a, jax.core.Tracer) for _, a in acc_pairs))
        if concrete:
            # eager: one jitted finisher (mean + cast for every param
            # in one dispatch, accumulators donated)
            fin = _accum_finish_exec(
                int(n_total),
                tuple(str(p.data.dtype) for p, _ in acc_pairs))
            gs, loss_mean = fin([a for _, a in acc_pairs],
                                jnp.asarray(loss_sum))
        else:
            # traced (graph step): the same expressions inline — the
            # division/cast are elementwise, so both branches are
            # bit-identical
            with jax.named_scope("opt/accum"):
                gs = [(a / nf).astype(p.data.dtype)
                      for p, a in acc_pairs]
                loss_mean = jnp.asarray(loss_sum).astype(
                    jnp.float32) / nf
        pairs = []
        for (p, _), g in zip(acc_pairs, gs):
            gt = tensor_mod.from_raw(g, p.device)
            # fresh output of the accumulation program: nothing else
            # references the buffer, so the fused update may donate it
            gt._donatable = True
            pairs.append((p, gt))
        dev = pairs[0][0].device if pairs else None
        loss_t = tensor_mod.from_raw(loss_mean, dev)
        if not isinstance(loss_mean, jax.core.Tracer):
            # eager path: count here; the graph step counts per
            # executed replay in _JitStep.__call__ instead (a trace
            # is not a step)
            stats_mod.count_accum_step()
        return self.apply_gradients(loss_t, pairs)

    def backward_and_update(self, loss: Tensor):
        """Reference: `opt.SGD.backward_and_update` — run autograd and
        apply updates per (param, grad) pair in emission order (with
        optional global-norm clipping, which buffers the pairs first
        but preserves the deterministic update order).

        Resilience hooks (singa_tpu.resilience): under dynamic loss
        scaling the backward seed is the live scale instead of ones;
        under the step guard the fused eager update (or, traced inside
        a graph-mode step, `_guarded_traced_update`) folds the
        all-finite check + skip-select into the compiled program.

        Under gradient-accumulation capture (`_accum_begin`) the
        backward still runs — with the scaled seed, so accumulated
        grads carry the scale exactly once — but the apply is
        deferred: (loss, pairs) is stashed for `apply_accumulated`
        and neither the optimizer step counter nor the guard state
        advances here."""
        if (self._accum_capture is not None
                and getattr(self, "_accum_skip_backward", False)):
            # scan-level remat capture: the caller owns the backward
            # (jax.vjp over the checkpointed region) — record only
            # that ONE backward_and_update fired and hand the loss back
            self._accum_capture.append((loss, None))
            return loss
        guard = resilience.guard_active()
        dy = None
        if guard and resilience.scaler_active():
            with jax.named_scope("opt/loss_scale"):
                dy = resilience.scaled_seed(loss.data)
        pairs = list(autograd.iter_backward(loss, dy))
        if self._accum_capture is not None:
            self._accum_capture.append((loss, pairs))
            return loss
        return self.apply_gradients(loss, pairs)

    def apply_gradients(self, loss: Tensor, pairs):
        """The post-backward half of `backward_and_update`: apply one
        optimizer step to explicit (param, grad) pairs — fused eager
        executable on concrete arrays, guard-folded traced updates
        inside a jit trace — advancing the step counter once. Shared
        by the normal backward path and `apply_accumulated`."""
        guard = resilience.guard_active()
        eager = True
        for p, g in pairs:
            if (isinstance(p.data, jax.core.Tracer)
                    or isinstance(
                        g.data if isinstance(g, Tensor) else g,
                        jax.core.Tracer)):
                eager = False
                break
        if eager and pairs:
            # one jitted executable for ALL param updates (VERDICT r4
            # next #7) instead of one dispatch per param; global-norm
            # clipping happens INSIDE the same program (the fused
            # trace reads self.clip_norm, which is part of the cache
            # key)
            self._fused_eager_update_all(pairs, clip=True,
                                         loss=loss if guard else None)
            self.step()
            return loss
        if guard and pairs:
            # graph mode: train_one_batch is being traced — fold the
            # guard into the surrounding jit program directly
            self._guarded_traced_update(loss, pairs)
            self.step()
            return loss
        if self.clip_norm is None:
            for p, g in pairs:
                self.update(p, g)
            self.step()
            return loss
        raw = [(p, g.data if isinstance(g, Tensor) else g)
               for p, g in pairs]
        with jax.named_scope("opt/clip"):
            scale = _global_clip_scale(self.clip_norm,
                                       [g for _, g in raw])
            raw = [(p, (g.astype(jnp.float32) * scale).astype(g.dtype))
                   for p, g in raw]
        for p, g in raw:
            self.update(p, g)
        self.step()
        return loss

    def _guarded_traced_update(self, loss: Tensor, pairs) -> None:
        """Step-guarded updates for the traced (graph-mode) path: the
        caller is already inside the whole-step jit trace, so the
        finite-check → `lax.cond(apply, skip)` sequence written here
        compiles into that one program — the skip branch is free, the
        unscale/clip work lives only in the apply branch, and the
        param/slot donation of `_JitStep` stays intact (an output-side
        where-select would pin every pre-step buffer to program end).
        `_JitStep` threads the guard state (scale + counters) through
        the program as traced arrays alongside the optimizer slots.
        Under GSPMD the finite bit reduces over the GLOBAL gradient
        values, so the replicated predicate is identical on every
        rank. Falls back to where-selects when `apply` changes the
        slot structure mid-trace (no `_ensure_opt_slots` ran).

        KEEP IN LOCKSTEP with the guarded `pure` in
        `_fused_eager_update_all`: identical finite-bit/unscale/
        cond/fallback/advance semantics — the policy math is shared
        via `resilience.all_finite`/`advance_state`, only the
        orchestration differs."""
        prepared = []
        for p, g in pairs:
            g = g.data if isinstance(g, Tensor) else g
            if g.dtype != p.data.dtype:
                with jax.named_scope(self._update_scope(p)):
                    g = g.astype(p.data.dtype)
            prepared.append((p, g))
        scale, counters = resilience.state_arrays()
        scaler = resilience.scaler_active()
        gs_raw = [g for _, g in prepared]
        with jax.named_scope("opt/guard"):
            finite = resilience.all_finite([loss.data] + gs_raw)
        pids = [id(p) for p, _ in prepared]
        names = [tuple(sorted(self.states.get(pid, ())))
                 for pid in pids]
        vals_in = [p.data for p, _ in prepared]
        slots_in = [[self.states[pid][n] for n in nm] if nm else []
                    for pid, nm in zip(pids, names)]

        def _prep_gs(gs):
            if scaler:
                # finite(g) == finite(g/s): checked on raw grads, only
                # the apply path pays the unscale
                with jax.named_scope("opt/loss_scale"):
                    inv = 1.0 / scale
                    gs = [g * inv.astype(g.dtype) for g in gs]
            if self.clip_norm is not None:
                with jax.named_scope("opt/clip"):
                    cs = _global_clip_scale(self.clip_norm, gs)
                    gs = [(g.astype(jnp.float32) * cs).astype(g.dtype)
                          for g in gs]
            return gs

        def apply_branch(op):
            vals, gs, slots = op
            gs = _prep_gs(gs)
            saved = {pid: self.states.get(pid) for pid in pids}
            try:
                new_vals, new_slots = [], []
                for (p, _), pid, nm, v, g, sl in zip(
                        prepared, pids, names, vals, gs, slots):
                    self.states[pid] = dict(zip(nm, sl))
                    with jax.named_scope(self._update_scope(p)):
                        new_vals.append(self._apply_masterized(p, v, g))
                    st = self.states[pid]
                    new_slots.append([st[n] for n in sorted(st)])
                return new_vals, new_slots
            finally:
                for pid in pids:
                    if saved[pid] is None:
                        self.states.pop(pid, None)
                    else:
                        self.states[pid] = saved[pid]

        def skip_branch(op):
            vals, gs, slots = op
            return list(vals), [list(sl) for sl in slots]

        try:
            with jax.named_scope("opt/guard"):
                new_vals, new_slots = jax.lax.cond(
                    finite, apply_branch, skip_branch,
                    (vals_in, gs_raw, slots_in))
        except (TypeError, ValueError):
            # apply created/renamed slots mid-trace: branch structures
            # can't match — run the update and select outputs instead
            gs = _prep_gs(gs_raw)
            old_slots = {pid: dict(self.states.get(pid, ()))
                         for pid in pids}
            for (p, _), g in zip(prepared, gs):
                with jax.named_scope(self._update_scope(p)):
                    p.data = self._apply_masterized(p, p.data, g)
            with jax.named_scope("opt/guard"):
                for (p, _), old in zip(prepared, vals_in):
                    p.data = jnp.where(finite, p.data, old)
                for pid in pids:
                    st = self.states.get(pid)
                    if not st:
                        continue
                    old = old_slots[pid]
                    for name in list(st):
                        st[name] = jnp.where(
                            finite, st[name],
                            old.get(name, jnp.zeros_like(st[name])))
        else:
            for (p, _), v in zip(prepared, new_vals):
                p.data = v
            for pid, nm, ns in zip(pids, names, new_slots):
                if nm:
                    self.states[pid] = dict(zip(nm, ns))
        # Guard state advances inside the trace — but only when the
        # state arrays ARE part of it (bound by _JitStep). Guard
        # enabled after compile leaves them concrete: advancing would
        # leak tracers into host state, so freeze + warn instead.
        if (isinstance(finite, jax.core.Tracer)
                and not isinstance(scale, jax.core.Tracer)):
            resilience.warn_frozen_guard_state()
            return
        with jax.named_scope("opt/guard"):
            resilience.bind_state_arrays(
                resilience.advance_state(finite, scale, counters))

    # -- state I/O for checkpointing ---------------------------------------
    def state_arrays(self) -> List:
        out = []
        for pstate in self.states.values():
            for k in sorted(pstate):
                out.append(pstate[k])
        return out

    def set_state_arrays(self, arrays: List) -> None:
        i = 0
        for pstate in self.states.values():
            for k in sorted(pstate):
                pstate[k] = arrays[i]
                i += 1


class SGD(Optimizer):
    """Reference: `opt.SGD(lr, momentum, dampening, weight_decay, nesterov)`.

    update: g += wd*p; buf = m*buf + (1-dampening)*g;
            g = g + m*buf (nesterov) | buf; p -= lr*g
    """

    def __init__(self, lr=0.1, momentum=0.0, dampening=0.0, weight_decay=0.0,
                 nesterov=False):
        super().__init__(lr)
        self.momentum = momentum
        self.dampening = dampening
        self.weight_decay = weight_decay
        self.nesterov = nesterov
        if nesterov and (momentum <= 0 or dampening != 0):
            raise ValueError("nesterov momentum requires momentum>0, dampening=0")

    def apply(self, param, value, grad):
        if self.weight_decay:
            grad = grad + self.weight_decay * value
        lr = self.lr_value
        if self.momentum:
            st = self.states.setdefault(id(param), {})
            buf = st.get("momentum_buf")
            if buf is None:
                buf = grad
            else:
                buf = self.momentum * buf + (1.0 - self.dampening) * grad
            buf = self._store_slot(st, "momentum_buf", buf, value.dtype)
            grad = grad + self.momentum * buf if self.nesterov else buf
        return value - lr * grad


class RMSProp(Optimizer):
    """Reference: `opt.RMSProp(lr, rho, epsilon, weight_decay)`."""

    def __init__(self, lr=0.1, rho=0.9, epsilon=1e-8, weight_decay=0.0):
        super().__init__(lr)
        self.rho = rho
        self.epsilon = epsilon
        self.weight_decay = weight_decay

    def apply(self, param, value, grad):
        if self.weight_decay:
            grad = grad + self.weight_decay * value
        st = self.states.setdefault(id(param), {})
        r = st.get("running_avg", jnp.zeros_like(value))
        r = self.rho * r + (1.0 - self.rho) * jnp.square(grad)
        r = self._store_slot(st, "running_avg", r, value.dtype)
        return value - self.lr_value * grad / jnp.sqrt(r + self.epsilon)


class AdaGrad(Optimizer):
    """Reference: `opt.AdaGrad(lr, epsilon)`."""

    # `history` is a monotone sum of squares: at bf16's 8 mantissa
    # bits, h + g**2 == h as soon as h outgrows the per-step increment
    # by ~256x, silently freezing the effective lr. Excluded from
    # slot_dtype by default.
    _fragile_slots = ("history",)

    def __init__(self, lr=0.1, epsilon=1e-8, weight_decay=0.0):
        super().__init__(lr)
        self.epsilon = epsilon
        self.weight_decay = weight_decay

    def apply(self, param, value, grad):
        if self.weight_decay:
            grad = grad + self.weight_decay * value
        st = self.states.setdefault(id(param), {})
        h = st.get("history", jnp.zeros_like(value))
        h = h + jnp.square(grad)
        h = self._store_slot(st, "history", h, value.dtype)
        return value - self.lr_value * grad / jnp.sqrt(h + self.epsilon)


class Adam(Optimizer):
    """Reference: `opt.Adam(lr, beta1, beta2, epsilon, weight_decay)`."""

    def __init__(self, lr=0.001, beta_1=0.9, beta_2=0.999, epsilon=1e-8,
                 weight_decay=0.0):
        super().__init__(lr)
        self.beta_1 = beta_1
        self.beta_2 = beta_2
        self.epsilon = epsilon
        self.weight_decay = weight_decay

    def apply(self, param, value, grad):
        if self.weight_decay:
            grad = grad + self.weight_decay * value
        st = self.states.setdefault(id(param), {})
        m = st.get("m", jnp.zeros_like(value))
        v = st.get("v", jnp.zeros_like(value))
        m = self.beta_1 * m + (1.0 - self.beta_1) * grad
        v = self.beta_2 * v + (1.0 - self.beta_2) * jnp.square(grad)
        m = self._store_slot(st, "m", m, value.dtype)
        v = self._store_slot(st, "v", v, value.dtype)
        t = self.step_counter + 1
        mhat = m / (1.0 - self.beta_1 ** t)
        vhat = v / (1.0 - self.beta_2 ** t)
        return value - self.lr_value * mhat / (jnp.sqrt(vhat) + self.epsilon)


class AdamW(Adam):
    """Adam with DECOUPLED weight decay (Loshchilov & Hutter): the
    decay is applied directly to the parameter, scaled by the lr, not
    folded into the gradient/moments like `Adam(weight_decay=...)`.
    No reference equivalent; standard for the transformer workloads
    this framework adds."""

    def apply(self, param, value, grad):
        wd = self.weight_decay
        self.weight_decay = 0.0  # keep decay out of the moments
        try:
            new = super().apply(param, value, grad)
        finally:
            self.weight_decay = wd
        if wd:
            new = new - self.lr_value * wd * value
        return new


class DistOpt(Optimizer):
    """Distributed data-parallel optimizer wrapper.

    Reference: `opt.DistOpt` over the NCCL `Communicator`
    (src/io/communicator.cc): per-gradient allreduce with fusion
    buckets, fp16-compressed and sparse variants, lr scaled by world
    size. Here the communicator is `singa_tpu.dist.Communicator` —
    XLA collectives (psum over ICI) on a device mesh — and the high-
    throughput path is mesh-mode jit (`Model.compile` with a sharded
    batch), where XLA inserts the cross-replica reductions itself.
    """

    def __init__(self, opt: Optimizer, communicator=None, nccl_id=None,
                 local_rank: int = 0, world_size: Optional[int] = None,
                 buffSize: int = 4194304):
        super().__init__(opt.lr)
        self.opt = opt
        if communicator is None:
            from .dist import Communicator

            communicator = Communicator(local_rank=local_rank,
                                        world_size=world_size,
                                        nccl_id=nccl_id,
                                        buff_size=buffSize)
        self.communicator = communicator
        self.world_size = self.communicator.world_size

    # delegate state/step to the wrapped optimizer
    @property
    def states(self):  # type: ignore[override]
        return self.opt.states

    @states.setter
    def states(self, v):
        pass  # base-class ctor writes; real states live on self.opt

    def update(self, param, grad):
        """Reference: `DistOpt.update` — allreduce then average then
        apply (same grad scaling as every backward_and_* path)."""
        with jax.named_scope("opt/allreduce"):
            self.all_reduce(grad)
            self.wait()
            inv = self.communicator.grad_scale
            if isinstance(grad, Tensor):
                grad.data = grad.data * inv
            else:
                grad = grad * inv
        self.opt.update(param, grad)

    def apply(self, param, value, grad):
        return self.opt.apply(param, value, grad)

    def set_slot_dtype(self, dtype, exclude=None):
        """Delegates to the wrapped optimizer (slots live there)."""
        self.opt.set_slot_dtype(dtype, exclude=exclude)
        return self

    def _accum_begin(self) -> None:
        """Gradient accumulation does not compose with the DistOpt
        driver regime (its backward_and_* variants stream per-grad
        allreduces from Python and never consult the capture hook —
        silently applying per microbatch would defeat the
        accumulation contract). Use mesh-mode
        `Model.compile(..., mesh=..., grad_accum=n)`, where the one
        SPMD program reduces once per accumulated step."""
        raise RuntimeError(
            "gradient accumulation is not supported with DistOpt; "
            "compile the model over a mesh "
            "(Model.compile(..., mesh=..., grad_accum=n)) instead")

    def slot_store_dtype(self, name, param):
        return self.opt.slot_store_dtype(name, param)

    def step(self):
        self.opt.step()

    @property
    def step_counter(self):
        return self.opt.step_counter

    @step_counter.setter
    def step_counter(self, v):
        if hasattr(self, "opt"):
            self.opt.step_counter = v

    def all_reduce(self, t):
        """Reference: `DistOpt.all_reduce` → `Communicator::synch`."""
        data = t.data if isinstance(t, Tensor) else t
        out = self.communicator.synch(data)
        if isinstance(t, Tensor):
            t.data = out
            return t
        return out

    def wait(self):
        self.communicator.wait()

    def backward_and_update(self, loss: Tensor, threshold: int = 2097152):
        """Reference: `DistOpt.backward_and_update` — small grads are
        fused into one flat buffer for a single allreduce, large grads
        go direct; grads averaged over world_size."""
        pairs = list(autograd.iter_backward(loss))
        small = [(p, g) for p, g in pairs if g.size() <= threshold]
        large = [(p, g) for p, g in pairs if g.size() > threshold]
        with jax.named_scope("opt/allreduce"):
            if small:
                reduced = self.communicator.fused_synch(
                    [g.data for _, g in small])
                for (p, g), r in zip(small, reduced):
                    g.data = r
            for _, g in large:
                g.data = self.communicator.synch(g.data)
            self.communicator.wait()
            inv = self.communicator.grad_scale
            for p, g in pairs:
                g.data = g.data * inv
        self._clip_pairs(pairs)
        if self._guard_skip(loss, pairs):
            self.opt.step()
            return loss
        for p, g in pairs:
            self.opt.update(p, g)
        self.opt.step()
        return loss

    def _guard_skip(self, loss, pairs) -> bool:
        """Driver-regime step guard (singa_tpu.resilience): the
        allreduced grads are identical on every rank, so a HOST-side
        finite check makes the same skip decision everywhere — one
        sync per step, which is already this regime's execution model.
        Dynamic loss scaling does not apply here (the seed is not
        scaled on the DistOpt paths); the partial/sparse variants
        bypass the guard like they bypass clipping (per-grad streaming
        by design). Returns True when the step must skip."""
        if not pairs or not resilience.guard_active():
            return False
        if resilience.scaler_active():
            resilience.warn_distopt_scaler()
        # Grads ONLY, not the loss: the grads are post-allreduce and
        # identical on every rank, but the loss is rank-LOCAL here —
        # a rank whose local loss overflowed while the reduced grads
        # stayed finite would skip alone and diverge the replicas.
        finite = resilience.host_all_finite(
            [g.data if isinstance(g, Tensor) else g
             for _, g in pairs])
        # with_scaler=False: this path never scaled the backward seed,
        # so growing/backing off the scale here would drift it away
        # from the gradients it protects on the scaled paths
        resilience.host_step_update(finite, with_scaler=False)
        return not finite

    def _clip_pairs(self, pairs):
        """Global-norm clip AFTER the allreduce (reduced grads are
        identical on every rank, so the clip factor is consistent);
        honors the wrapped optimizer's clip_norm."""
        cn = (self.opt.clip_norm if self.opt.clip_norm is not None
              else self.clip_norm)  # honor the wrapper's public API too
        if cn is None or not pairs:
            return
        with jax.named_scope("opt/clip"):
            scale = _global_clip_scale(cn, [g.data for _, g in pairs])
            for _, g in pairs:
                g.data = (g.data.astype(jnp.float32)
                          * scale).astype(g.data.dtype)

    def backward_and_update_half(self, loss: Tensor, threshold: int = 2097152):
        """Reference: `backward_and_update_half` — fp16 compression
        around the allreduce; here bf16 (the TPU-native half)."""
        pairs = list(autograd.iter_backward(loss))
        with jax.named_scope("opt/allreduce"):
            reduced = self.communicator.fused_synch_half(
                [g.data for _, g in pairs]
            )
            inv = self.communicator.grad_scale
            for (p, g), r in zip(pairs, reduced):
                g.data = r.astype(p.data.dtype) * inv
        self._clip_pairs(pairs)
        if self._guard_skip(loss, pairs):
            self.opt.step()
            return loss
        for p, g in pairs:
            self.opt.update(p, g)
        self.opt.step()
        return loss

    def backward_and_partial_update(self, loss: Tensor, threshold: int = 2097152):
        """Reference: `backward_and_partial_update` — round-robin: each
        step synchronizes only a rotating subset of params (saves
        bandwidth, params drift slightly)."""
        pairs = list(autograd.iter_backward(loss))
        k = self.opt.step_counter % max(len(pairs), 1)
        for i, (p, g) in enumerate(pairs):
            if i == k:
                with jax.named_scope("opt/allreduce"):
                    g.data = (self.communicator.synch(g.data)
                              * self.communicator.grad_scale)
            self.opt.update(p, g)
        self.opt.step()
        return loss

    def backward_and_sparse_update(self, loss: Tensor, spars: float = 0.05,
                                   topK: bool = False):
        """Reference: `backward_and_sparse_update` — threshold or top-K
        sparsified gradient exchange."""
        pairs = list(autograd.iter_backward(loss))
        inv = self.communicator.grad_scale
        for p, g in pairs:
            with jax.named_scope("opt/allreduce"):
                g.data = self.communicator.sparsification(
                    g.data, spars=spars, topK=topK
                ) * inv
            self.opt.update(p, g)
        self.opt.step()
        return loss
