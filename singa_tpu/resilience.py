"""Fault-tolerant training: step guard, dynamic loss scaling, fault
injection, and crash-consistent auto-resume.

The reference's only resilience primitive is `Device::SetSkipIteration`
(skip the first profiled iterations); everything else — a NaN gradient,
a truncated checkpoint, a dead device — corrupts state or kills the
run. The TPU-native design treats the STEP LOOP as the resilience
boundary (µ-cuDNN's decomposition mindset, PAPERS.md: recover at the
smallest unit that still has clean semantics):

  - **StepGuard** — an all-finite check on loss + gradients folded
    INTO the compiled step (the fused eager optimizer update in
    `opt.py`, and the `_JitStep`/`ShardedJitStep` graph program). A
    non-finite step selects the pre-step parameter/optimizer-slot
    values with `jnp.where` — no host round-trip on the hot path, the
    skip costs a handful of select ops. On a device mesh the finite
    bit is reduced over the GLOBAL gradient values inside the single
    SPMD program, so every rank makes the identical skip decision by
    construction. Enable: `device.set_step_guard(True)`.
  - **DynamicLossScaler** — the AMP companion: the backward seed is
    multiplied by a scale that grows ×`growth_factor` after
    `growth_interval` clean steps and backs off ×`backoff_factor` on
    overflow (the guard's finite bit). Power-of-two factors keep the
    scale/unscale round trip bit-exact. Enable:
    `device.set_loss_scaling(...)`; implies the step guard.
  - **FaultInjector** — deterministic, seed-keyed injection of NaN
    batches/grads, optimizer-state corruption, checkpoint truncation/
    bit-rot, and simulated device loss. `tests/test_resilience.py`
    uses it to prove the guarantees on CPU.
  - **run_resumable** — the crash-consistent training loop over
    `checkpoint.CheckpointManager` (content-digest manifests,
    validate-and-fall-back `restore_latest`): kill mid-run, restart,
    and the loss trajectory matches the uninterrupted run.

Counters surface via `cache_stats()["resilience"]` (snapshot reads
device scalars — the host sync happens at observability time, never
inside the step). Guard state (scale + counters) is threaded through
compiled programs as traced arrays, exactly like optimizer slots, and
is checkpointed in the zip meta so resume keeps the backoff history.
"""
from __future__ import annotations

import hashlib
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from . import stats as stats_mod

__all__ = [
    "guard_active",
    "scaler_active",
    "scaling_config",
    "config_key",
    "state_arrays",
    "bind_state_arrays",
    "reset_state",
    "all_finite",
    "host_all_finite",
    "advance_state",
    "host_step_update",
    "scaled_seed",
    "export_host_state",
    "import_host_state",
    "DeviceLostError",
    "FaultInjector",
    "backoff_delay_s",
    "run_resumable",
]

# Counter layout in the int32[5] state vector (index -> meaning).
_APPLIED, _SKIPPED, _STREAK, _GROWTHS, _BACKOFFS = range(5)

# Live guard state: [scale f32 scalar, counters int32[5]]. Built
# lazily so importing the module never touches a jax backend.
_STATE: Optional[List] = None

_warned_frozen = False


# ---------------------------------------------------------------------------
# Config accessors (state owned by singa_tpu.stats; user-facing setters
# on singa_tpu.device — the reference's config surface).
# ---------------------------------------------------------------------------
def guard_active() -> bool:
    """Step guard on? (loss scaling implies it: the scaler needs the
    finite bit for backoff, and unscaled-but-unguarded updates would
    apply overflowed gradients)."""
    cfg = stats_mod.get_config()
    return bool(cfg["step_guard"]) or cfg["loss_scaling"] is not None


def scaler_active() -> bool:
    return stats_mod.get_config()["loss_scaling"] is not None


def scaling_config() -> Optional[Dict]:
    return stats_mod.get_config()["loss_scaling"]


def config_key():
    """Hashable snapshot for executable-cache keys: toggling the guard
    or mutating scaler hyperparameters must retrace, not reuse a
    program with the old policy baked in. None when inactive."""
    if not guard_active():
        return None
    cfg = scaling_config()
    return ("guard", None if cfg is None
            else tuple(sorted(cfg.items())))


# ---------------------------------------------------------------------------
# Guard state: threaded through compiled steps like optimizer slots.
# ---------------------------------------------------------------------------
def _ensure_state() -> List:
    global _STATE
    if _STATE is None:
        cfg = scaling_config()
        init = float(cfg["init_scale"]) if cfg else 1.0
        _STATE = [jnp.asarray(init, jnp.float32),
                  jnp.zeros((5,), jnp.int32)]
    return _STATE


def state_arrays() -> List:
    """[scale, counters] — the traced-state contract `_JitStep` and the
    fused eager update thread through their programs."""
    return list(_ensure_state())


def bind_state_arrays(arrays) -> None:
    global _STATE
    scale, counters = arrays
    _STATE = [scale, counters]


def reset_state() -> None:
    """Drop guard state; rebuilt from the live config on next access.
    Called by `device.set_loss_scaling` so a new scale policy starts
    from its own init_scale."""
    global _STATE
    _STATE = None


# ---------------------------------------------------------------------------
# The guard math (pure jnp: runs traced inside jit AND eagerly for the
# DistOpt driver paths).
# ---------------------------------------------------------------------------
def all_finite(arrays, axis_name: Optional[str] = None):
    """Scalar bool: every inexact array is all-finite. Integer arrays
    are skipped (always finite). Inside a GSPMD program the reduction
    runs over the GLOBAL sharded values, so every rank sees the same
    bit; pass `axis_name` to reduce explicitly under shard_map/pmap."""
    ok = None
    for a in arrays:
        if a is None:
            continue
        if not jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact):
            continue
        bit = jnp.isfinite(a).all()
        ok = bit if ok is None else ok & bit
    if ok is None:
        ok = jnp.asarray(True)
    if axis_name is not None:
        from jax import lax

        ok = lax.pmin(ok.astype(jnp.int32), axis_name).astype(bool)
    return ok


def host_all_finite(arrays) -> bool:
    """Concrete-bool variant for the DistOpt driver regime: the
    reduction runs ON DEVICE (`all_finite`) and only the one-byte
    result syncs to host — never the gradient bytes themselves. A
    sync per step is already that regime's execution model."""
    return bool(np.asarray(all_finite(arrays)))


def advance_state(finite, scale, counters) -> Tuple:
    """Next (scale, counters) given this step's finite bit. Pure jnp —
    folds into the compiled step; the scaler branch is baked from the
    config at trace time (config changes retrace via `config_key`)."""
    finite = jnp.asarray(finite)
    fi = finite.astype(jnp.int32)
    applied = counters[_APPLIED] + fi
    skipped = counters[_SKIPPED] + (1 - fi)
    cfg = scaling_config()
    # The clean-step streak advances whenever the guard runs (it is a
    # guard counter — steps since the last non-finite step — not a
    # scaler-only quantity); only the growth/backoff logic is gated on
    # the scaler config.
    streak_next = jnp.where(finite, counters[_STREAK] + 1, 0)
    if cfg is None:
        new_scale = scale
        streak = streak_next
        growths = counters[_GROWTHS]
        backoffs = counters[_BACKOFFS]
    else:
        interval = int(cfg["growth_interval"])
        if interval > 0:
            grow = finite & (streak_next >= interval)
        else:
            grow = jnp.asarray(False)
        backed = jnp.maximum(scale * cfg["backoff_factor"],
                             cfg["min_scale"])
        # Growth is capped at max_scale: zero-gradient params keep the
        # streak clean forever, and an uncapped scale would overflow
        # f32 to inf — from which backoff (inf * 0.5 == inf) could
        # never recover, stalling the run in permanent skip.
        grown = jnp.minimum(scale * cfg["growth_factor"],
                            cfg["max_scale"])
        new_scale = jnp.where(
            grow, grown, jnp.where(finite, scale, backed))
        streak = jnp.where(grow, 0, streak_next)
        growths = counters[_GROWTHS] + grow.astype(jnp.int32)
        backoffs = counters[_BACKOFFS] + (1 - fi)
    new_counters = jnp.stack(
        [applied, skipped, streak, growths, backoffs]).astype(jnp.int32)
    return new_scale.astype(jnp.float32), new_counters


def host_step_update(finite: bool, with_scaler: bool = True) -> None:
    """Advance guard state eagerly (DistOpt driver paths, where the
    skip decision is made host-side on the already-reduced grads).
    `with_scaler=False` advances the applied/skipped counters only —
    for paths that never scaled the backward seed, where growing or
    backing off the scale would desynchronize it from the gradients
    it is supposed to protect."""
    scale, counters = state_arrays()
    if with_scaler:
        bind_state_arrays(advance_state(jnp.asarray(bool(finite)),
                                        scale, counters))
        return
    c = np.asarray(counters).copy()
    c[_APPLIED if finite else _SKIPPED] += 1
    # the clean-step streak is a guard counter (see advance_state):
    # it tracks steps-since-last-skip on every guarded path
    c[_STREAK] = c[_STREAK] + 1 if finite else 0
    bind_state_arrays([scale, jnp.asarray(c)])


def scaled_seed(loss_data):
    """The backward seed dL/dL under loss scaling: `scale` broadcast to
    the loss shape/dtype (instead of the implicit ones). Power-of-two
    scales make scale→unscale an exact exponent shift."""
    scale, _ = state_arrays()
    return jnp.broadcast_to(scale.astype(loss_data.dtype),
                            loss_data.shape)


def annotate_exception(e: BaseException, note: str) -> None:
    """Attach context to an exception without changing its type:
    PEP-678 notes when available (py3.11+), args-append otherwise
    (existing `except <Type>` handlers keep working either way). The
    shared idiom behind checkpoint-writer and prefetch-worker error
    reporting."""
    if hasattr(e, "add_note"):
        e.add_note(note)
        return
    try:
        e.args = tuple(e.args) + (note,)
    except Exception:
        pass


_warned_distopt_scaler = False


def warn_distopt_scaler() -> None:
    """One-time warning: loss scaling is configured but the DistOpt
    driver path never scales the backward seed — the scale is frozen
    there so it cannot drift away from the gradients it protects."""
    global _warned_distopt_scaler
    if not _warned_distopt_scaler:
        _warned_distopt_scaler = True
        print("singa_tpu: dynamic loss scaling does not apply on the "
              "DistOpt driver paths (backward seed is unscaled); the "
              "scale stays frozen there — use mesh-mode compile for "
              "scaled multi-chip training", file=sys.stderr)


def warn_frozen_guard_state() -> None:
    """One-time warning: guard math traced while the state arrays are
    concrete (guard enabled AFTER the step was compiled) — the scale
    is baked as a constant and counters cannot advance until the model
    is re-compile()d."""
    global _warned_frozen
    if not _warned_frozen:
        _warned_frozen = True
        print("singa_tpu: step guard enabled after the train step was "
              "compiled; guard counters/scale are frozen until "
              "model.compile() rebuilds the step", file=sys.stderr)


# ---------------------------------------------------------------------------
# Checkpoint round-trip (host values in the zip meta).
# ---------------------------------------------------------------------------
def export_host_state() -> Dict:
    scale, counters = state_arrays()
    return {"loss_scale": float(np.asarray(scale)),
            "counters": [int(x) for x in np.asarray(counters)]}


def import_host_state(d: Optional[Dict]) -> None:
    if not d:
        return
    bind_state_arrays([
        jnp.asarray(float(d.get("loss_scale", 1.0)), jnp.float32),
        jnp.asarray(np.asarray(d.get("counters", [0] * 5),
                               np.int32))])


# ---------------------------------------------------------------------------
# Observability: cache_stats()["resilience"].
# ---------------------------------------------------------------------------
class _ResilienceStats:
    """Snapshot provider for the stats registry. Reads the device
    scalars (host sync) — observability-time cost only."""

    def snapshot(self) -> Dict:
        cfg = scaling_config()
        out = {
            "enabled": guard_active(),
            "loss_scaling": cfg is not None,
        }
        if _STATE is None:
            # nothing has run under the guard yet: report the config
            # view without materializing device state (cache_stats()
            # must not touch a jax backend for a disabled feature)
            out.update({
                "loss_scale": float(cfg["init_scale"]) if cfg else 1.0,
                "steps_applied": 0, "steps_skipped": 0,
                "good_streak": 0, "scale_growths": 0,
                "scale_backoffs": 0,
            })
            return out
        scale, counters = state_arrays()
        c = np.asarray(counters)
        out.update({
            "loss_scale": float(np.asarray(scale)),
            "steps_applied": int(c[_APPLIED]),
            "steps_skipped": int(c[_SKIPPED]),
            "good_streak": int(c[_STREAK]),
            "scale_growths": int(c[_GROWTHS]),
            "scale_backoffs": int(c[_BACKOFFS]),
        })
        return out

    def reset(self) -> None:
        # Observability reset must not change training behavior (the
        # same contract as the trace caches): zero the COUNTERS but
        # keep the live loss scale and growth streak — they are
        # optimizer state, not observability. `reset_state()` is the
        # explicit way to reinitialize the scale.
        global _STATE
        if _STATE is None:
            return
        scale, counters = _STATE
        c = np.asarray(counters).copy()
        c[_APPLIED] = c[_SKIPPED] = c[_GROWTHS] = c[_BACKOFFS] = 0
        _STATE = [scale, jnp.asarray(c)]


stats_mod.register_cache("resilience", _ResilienceStats())


# ---------------------------------------------------------------------------
# Fault injection: deterministic, seed-keyed.
# ---------------------------------------------------------------------------
class DeviceLostError(RuntimeError):
    """Simulated device loss (the PJRT client dying mid-run)."""


class FaultInjector:
    """Deterministic fault source for resilience tests and chaos runs.

    `schedule` maps fault kind -> either an iterable of explicit step
    numbers or a float probability in [0, 1]. Probabilistic faults are
    keyed by sha256(seed, kind, step), so the same (seed, schedule)
    produces the same fault sequence on every run and every rank —
    injection never introduces cross-rank divergence itself.

    Kinds used by the in-tree tests: "nan_batch", "nan_grad",
    "opt_state", "ckpt_truncate", "device_loss".

    Serving kinds (ISSUE 8; consumed by `serve.ServingEngine`'s
    test-only `_chaos_attempt` / dispatcher-loop hooks, keyed by the
    global dispatch-attempt / coalesce-cycle index so retries redraw):
    "dispatch_fail" (transient dispatch error), "dispatch_hang"
    (dispatch sleeps `hang_s` before proceeding), "poison_request"
    (keyed by submit ordinal: the marked request fails EVERY dispatch
    it rides in — the bisection target), "device_lost_serve"
    (`DeviceLostError` from the dispatch), "dispatcher_kill" (the
    dispatcher loop itself dies — the supervision target).

    Fleet kinds (ISSUE 11; consumed by `fleet.FleetRouter`'s
    `_chaos_route` hook, keyed by the ROUTER submit ordinal and
    applied to the replica that request just routed to):
    "replica_kill" (hard replica death — queued futures fail loudly
    and reroute via failover; the fleet-supervision target),
    "replica_hang" (the replica's next dispatch sleeps `hang_s`),
    "stale_health" (the replica's health snapshot freezes and ages
    into ejection — the wedged-writer scenario `health_max_age_s`
    exists for).

    Process-transport kinds (ISSUE 13; consumed by the same
    `_chaos_route` hook, meaningful on `fleet_proc.ProcReplica`
    handles): "proc_sigkill" (a REAL `os.kill(pid, SIGKILL)` of the
    worker — detection via reader EOF/child exit code and supervisor
    respawn must be observed, not arranged), "proc_hang" (the
    worker's next dispatch sleeps `hang_s`, armed over the wire),
    "pipe_stall" (the parent's next frame write stalls — the IPC
    deadline/backpressure target), "torn_frame" (the worker corrupts
    its next reply frame — the CRC check must refuse it; a truncated
    reply can never be delivered as data).
    """

    def __init__(self, seed: int = 0, schedule: Optional[Dict] = None,
                 hang_s: float = 0.05):
        self.seed = int(seed)
        self.hang_s = float(hang_s)
        self.schedule: Dict = {}
        for kind, spec in (schedule or {}).items():
            if isinstance(spec, (int, float)) and not isinstance(
                    spec, bool):
                spec = float(spec)
                if not 0.0 <= spec <= 1.0:
                    raise ValueError(
                        f"probability for {kind!r} must be in [0,1]")
                self.schedule[kind] = spec
            else:
                self.schedule[kind] = frozenset(int(s) for s in spec)

    def _unit(self, kind: str, step: int) -> float:
        h = hashlib.sha256(
            f"{self.seed}/{kind}/{step}".encode()).digest()
        return int.from_bytes(h[:8], "big") / float(2 ** 64)

    def should(self, kind: str, step: int) -> bool:
        spec = self.schedule.get(kind)
        if spec is None:
            return False
        if isinstance(spec, frozenset):
            return int(step) in spec
        return self._unit(kind, int(step)) < spec

    # -- injection actions -------------------------------------------------
    def nan_batch(self, x, step: int):
        """Return `x` with one NaN element when scheduled (identity
        otherwise). Works on Tensors and raw arrays, eager or traced —
        a poisoned input drives loss AND grads non-finite through the
        real forward/backward, which is how NaNs arrive in practice."""
        if not self.should("nan_batch", step):
            return x
        data = x.data if hasattr(x, "data") else x
        flat = jnp.ravel(data).at[0].set(jnp.nan).reshape(data.shape)
        if hasattr(x, "data"):
            out = x.clone() if hasattr(x, "clone") else x
            out.data = flat
            return out
        return flat

    def corrupt_grads(self, pairs, step: int):
        """Poison the first gradient of `pairs` with NaN in place."""
        if not self.should("nan_grad", step) or not pairs:
            return pairs
        p, g = pairs[0]
        data = g.data if hasattr(g, "data") else g
        bad = data * jnp.nan
        if hasattr(g, "data"):
            g.data = bad
        else:
            pairs[0] = (p, bad)
        return pairs

    def corrupt_optimizer_state(self, opt, step: int) -> bool:
        """Write NaN into the first optimizer slot (True if it did)."""
        if not self.should("opt_state", step):
            return False
        for pstate in opt.states.values():
            for name in sorted(pstate):
                pstate[name] = pstate[name] * jnp.nan
                return True
        return False

    def truncate_checkpoint(self, path: str, frac: float = 0.5) -> None:
        """Truncate a checkpoint file to `frac` of its bytes — the
        classic kill-mid-write artifact (minus the atomic-rename
        protection, i.e. what a non-atomic writer would leave)."""
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(max(1, int(size * frac)))

    def corrupt_checkpoint(self, path: str) -> None:
        """Flip bytes mid-file without changing the size (silent
        bit-rot: only a content digest catches it)."""
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.seek(size // 2)
            chunk = f.read(8)
            f.seek(size // 2)
            f.write(bytes(b ^ 0xFF for b in chunk))

    def check_device_loss(self, step: int) -> None:
        """Raise `DeviceLostError` when scheduled (call from the train
        loop to simulate the chip disappearing mid-run)."""
        if self.should("device_loss", step):
            raise DeviceLostError(
                f"injected device loss at step {step}")


def backoff_delay_s(attempt: int, base_s: float, jitter: float = 0.5,
                    seed: int = 0, salt: str = "retry") -> float:
    """Exponential-backoff delay for retry `attempt` (1-based):
    `base_s * 2**(attempt-1)`, scaled by a DETERMINISTIC seed-keyed
    jitter in [1-jitter, 1+jitter] (the FaultInjector sha256 idiom) —
    retries decorrelate across workers without making any test run
    nondeterministic."""
    if base_s <= 0:
        return 0.0
    h = hashlib.sha256(f"{seed}/{salt}/{attempt}".encode()).digest()
    u = int.from_bytes(h[:8], "big") / float(2 ** 64)
    return base_s * (2.0 ** (max(int(attempt), 1) - 1)) * (
        1.0 + float(jitter) * (2.0 * u - 1.0))


# ---------------------------------------------------------------------------
# Crash-consistent auto-resume.
# ---------------------------------------------------------------------------
def run_resumable(model, manager, batch_fn: Callable[[int], tuple],
                  total_steps: int, save_every: int = 10,
                  aux_extra: Optional[Dict] = None,
                  metrics=None) -> Dict[int, float]:
    """Resumable training loop: restore the latest VALID checkpoint
    (corrupt/truncated newest ones are skipped, see
    `CheckpointManager.restore_latest`), then train steps
    `start+1 .. total_steps`, checkpointing every `save_every` steps
    and at the end.

    `batch_fn(step)` must return the (x, y) batch for that step — a
    deterministic function of the step number is what makes the
    resumed loss trajectory match the uninterrupted run exactly.

    Observability (singa_tpu.trace): each step runs under a
    `trace.step_span` whose children decompose it — data_wait (the
    batch_fn call, plus any BatchIter wait inside it), the model's
    dispatch/device_sync spans, and checkpoint_save/checkpoint_restore
    around manager I/O. `metrics` (a `trace.MetricsLogger`) appends
    one structured JSONL record per executed step (loss, examples/sec,
    the span timings, cache/resilience/accum counters) — the record is
    flushed before the step's checkpoint can publish, so a killed run
    keeps a log at least as far as its last durable checkpoint.

    Returns {step: loss} for the steps THIS invocation ran. A fresh
    process that crashed mid-run calls this again with the same
    arguments and continues where the last durable checkpoint left
    off; also exposed as `Model.fit_resumable`.
    """
    from . import trace as trace_mod

    with trace_mod.span("checkpoint_restore"):
        start, _aux = manager.restore_latest(model)
    start = 0 if start is None else int(start)
    losses: Dict[int, float] = {}
    for step in range(start + 1, int(total_steps) + 1):
        t0 = time.perf_counter()
        with trace_mod.step_span(step):
            with trace_mod.span("data_wait"):
                x, y = batch_fn(step)
            _, loss = model(x, y)
            with trace_mod.span("device_sync"):
                losses[step] = float(np.asarray(
                    loss.to_numpy() if hasattr(loss, "to_numpy")
                    else loss))
        if metrics is not None:
            shape = getattr(x, "shape", None)
            metrics.log_step(
                step, loss=losses[step],
                examples=shape[0] if shape else None,
                step_s=time.perf_counter() - t0)
        if step % save_every == 0 or step == total_steps:
            aux = {"resumable_step": step}
            if aux_extra:
                aux.update(aux_extra)
            with trace_mod.span("checkpoint_save"):
                manager.save(model, step=step, aux_states=aux)
    manager.wait_all()
    return losses
