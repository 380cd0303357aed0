"""Eager hot-path cache observability + policy config.

The eager path leans on three executable caches (SURVEY §7 hard-part
#4): the recorded-backward DAG cache (`autograd._DAG_BWD_CACHE`), the
per-op executable cache (`autograd._EXEC_CACHE`), and the fused
optimizer-update cache (`opt.Optimizer._fused_cache`). A retrace storm
in any of them silently turns a µs-dispatch step into a ms-trace step;
this module makes that visible instead of guessable:

  - `CacheStats` — per-cache hit/miss/evict/retrace counters plus
    trace-time accounting;
  - `TieredLRUCache` — the DAG backward cache's container: LRU with
    hit promotion (a hot executable cycling among >capacity shapes
    stays resident) and *tiered* eviction — negative entries (a trace
    that failed once; cheap to rediscover) are evicted before positive
    compiled executables (expensive to re-pay);
  - `cache_stats()` — one snapshot dict over every registered cache,
    plumbed through `Model.cache_stats()` and written into every
    `trace.MetricsLogger` record;
  - the eager config knobs (`dag_cache_capacity`, `dag_cache_policy`,
    `buffer_donation`), owned here so `device`, `autograd`, and `opt`
    can share them without an import cycle. User-facing setters live
    on `singa_tpu.device` (the reference's config surface).

µ-cuDNN (arXiv:1804.04806) and TVM (arXiv:1802.04799) make the same
point from both sides: framework-level caching decisions around a
fixed kernel library dominate end-to-end throughput, and compiled
artifacts must be cached on program structure — so the cache layer is
a first-class, observable subsystem here, not an implementation detail.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, Optional

__all__ = [
    "CacheStats",
    "TieredLRUCache",
    "cache_stats",
    "reset_cache_stats",
    "register_cache",
    "configure",
    "get_config",
    "donation_enabled",
    "bn_stats_dtype",
    "dag_auto_flops_per_op",
    "count_train_step",
    "grad_accum_n",
    "remat_policy",
    "REMAT_POLICIES",
    "note_accum_build",
    "count_accum_step",
    "moe_capacity_factor",
    "pipeline_microbatches",
    "note_pipeline_build",
    "note_moe_build",
    "note_moe_dropped",
    "note_collective",
]


# ---------------------------------------------------------------------------
# Eager policy config (user-facing setters: singa_tpu.device).
# ---------------------------------------------------------------------------
_CONFIG: Dict = {
    # Max entries in the recorded-backward DAG cache (was a hard-coded
    # 256 FIFO before this subsystem existed).
    "dag_cache_capacity": 256,
    # "lru": promote on hit (default). "fifo": insertion order only —
    # kept for A/B measurement (tests/test_cache_stats.py shows the
    # retrace storm it causes on cycling workloads).
    "dag_cache_policy": "lru",
    # Donate param/momentum/grad buffers into the jitted optimizer
    # update (and the graph-mode step): XLA reuses the memory in place
    # instead of round-tripping fresh allocations.
    "buffer_donation": True,
    # BatchNorm statistics precision floor (the byte-diet knob).
    # None = promote to at-least-fp32 (the reference-parity default);
    # "bfloat16"/"float16" lower the floor so bf16-AMP activations are
    # normalized WITHOUT materializing an fp32 copy that round-trips
    # HBM (BASELINE.md roofline: BN stat traffic is a named byte
    # lever). Inputs are never DOWNcast — fp32 activations keep fp32
    # stats under any floor. Setter: device.set_bn_stats_dtype.
    "bn_stats_dtype": None,
    # Recorded-backward auto-routing threshold: DAGs whose estimated
    # mean FLOPs/op exceed this are compute-bound (conv nets) — the
    # per-op walk's dispatch overhead is noise there, so they skip the
    # recorded path's trace + cache residency. Trace-bound DAGs (small
    # matmul/elementwise chains) stay on the one-dispatch replay.
    "dag_auto_flops_per_op": 2e7,
    # Resilience (singa_tpu.resilience): fold an all-finite check on
    # loss+grads into the compiled step; a non-finite step skips the
    # param/slot update via on-device selects (no host round-trip).
    # Setter: device.set_step_guard. Loss scaling below implies it.
    "step_guard": False,
    # Dynamic loss scaling for the AMP path: None = off, else a dict
    # {init_scale, growth_factor, backoff_factor, growth_interval,
    # min_scale} (normalized by configure). Setter:
    # device.set_loss_scaling.
    "loss_scaling": None,
    # Scan-level rematerialization policy (ISSUE 9): None = off, else
    # a named jax.checkpoint policy ("dots_saveable",
    # "nothing_saveable", "everything_saveable",
    # "dots_with_no_batch_dims_saveable") or a
    # ("save_anything_but_these_names", [names...]) pair. When armed,
    # the graph-mode step derives each microbatch's gradients from
    # `jax.vjp` over the WHOLE forward+loss region wrapped in
    # `jax.checkpoint(policy=...)` — inside `_JitStep._accum_step`'s
    # lax.scan body (and, with grad_accum off, the step body runs as
    # one microbatch) — so XLA recomputes non-saveable activations in
    # the backward instead of keeping them live across the fwd→bwd
    # boundary. Composes with the per-op `autograd.set_remat` (which
    # checkpoints individual op fns) and with grad accumulation (fp32
    # accumulation preserved). Eager mode ignores it (there is no
    # compiled program whose liveness it could shape). Read at
    # executable build time: re-`compile()` after toggling. Setter:
    # device.set_remat_policy.
    "remat_policy": None,
    # Multi-axis parallel trainer overrides (ISSUE 10). Both default
    # to None = "use the layer/plan's own setting"; when set they
    # override every PipelineStack / MoE layer at trace time, which is
    # what lets the autotuner sweep them without rebuilding models.
    # Read at executable build/trace time (the grad_accum contract):
    # re-compile() after toggling. Setters ride
    # device.set_parallel_plan's module (parallel.plan) and the
    # autotuner's apply_config.
    #   pipeline_microbatches: microbatch count of every pipeline
    #   schedule (None = the stack's own setting, which defaults to
    #   the pipe size).
    "pipeline_microbatches": None,
    #   moe_capacity_factor: expert capacity factor of every MoE layer
    #   (None = the layer's constructor value).
    "moe_capacity_factor": None,
    # Microbatched gradient accumulation (ISSUE 4): the compiled train
    # step reshapes its batch to [n, mb, ...] and lax.scans the
    # forward/backward over microbatches, accumulating gradients in
    # fp32 and applying the optimizer ONCE on the mean — effective
    # batch beyond HBM, one gradient reduction per accumulated step on
    # a mesh. 1 = off. Read at executable build time (the same
    # contract as buffer_donation/step_guard): re-`compile()` an
    # already-compiled graph-mode model after toggling. Setter:
    # device.set_grad_accum; Model.compile(grad_accum=n) overrides
    # per-model.
    "grad_accum": 1,
    # Post-training quantization for the INFERENCE stack (ISSUE 19):
    # "off" = fp32 decode/forward (default), "int8" = symmetric
    # per-channel int8 weights + per-slot-scaled int8 KV slab with
    # dequant-at-use / fp32 accumulation (singa_tpu.quant). Read at
    # decode-program build time and part of the export-cache
    # fingerprint — flip ⇒ AOT miss, never a stale load. Training
    # paths ignore it. Setter: device.set_inference_quant.
    "inference_quant": "off",
}

_LOSS_SCALING_DEFAULTS = {
    "init_scale": 2.0 ** 15,
    "growth_factor": 2.0,
    "backoff_factor": 0.5,
    "growth_interval": 2000,
    "min_scale": 1.0,
    # Growth ceiling: all-zero grads keep the streak clean forever,
    # and an uncapped scale overflows f32 to inf, from which backoff
    # can never recover (inf * 0.5 == inf).
    "max_scale": 2.0 ** 24,
}


def configure(**kw) -> Dict:
    """Update eager-config knobs; returns the live config dict."""
    for k, v in kw.items():
        if k not in _CONFIG:
            raise KeyError(
                f"unknown eager config key {k!r}; known: {sorted(_CONFIG)}")
        if k == "dag_cache_capacity":
            v = int(v)
            if v < 1:
                raise ValueError("dag_cache_capacity must be >= 1")
        elif k == "dag_cache_policy":
            if v not in ("lru", "fifo"):
                raise ValueError("dag_cache_policy must be 'lru' or 'fifo'")
        elif k == "bn_stats_dtype":
            if v is not None:
                v = str(v)
                if v not in ("bfloat16", "float16"):
                    raise ValueError(
                        "bn_stats_dtype must be None, 'bfloat16' or "
                        "'float16'")
        elif k == "dag_auto_flops_per_op":
            v = float(v)
            if v <= 0:
                raise ValueError("dag_auto_flops_per_op must be > 0")
        elif k == "grad_accum":
            v = int(v)
            if v < 1:
                raise ValueError("grad_accum must be >= 1")
        elif k == "pipeline_microbatches":
            if v is not None:
                v = int(v)
                if v < 1:
                    raise ValueError(
                        "pipeline_microbatches must be None or >= 1")
        elif k == "moe_capacity_factor":
            if v is not None:
                v = float(v)
                if v <= 0:
                    raise ValueError(
                        "moe_capacity_factor must be None or > 0")
        elif k == "remat_policy":
            v = _normalize_remat_policy(v)
        elif k == "inference_quant":
            v = str(v)
            if v not in ("off", "int8"):
                raise ValueError(
                    "inference_quant must be 'off' or 'int8'")
        elif k == "loss_scaling":
            if v is not None:
                if not isinstance(v, dict):
                    raise ValueError(
                        "loss_scaling must be None or a dict of "
                        f"{sorted(_LOSS_SCALING_DEFAULTS)}")
                unknown = set(v) - set(_LOSS_SCALING_DEFAULTS)
                if unknown:
                    raise ValueError(
                        f"unknown loss_scaling keys {sorted(unknown)}")
                v = {**_LOSS_SCALING_DEFAULTS, **v}
                v["growth_interval"] = int(v["growth_interval"])
                for fk in ("init_scale", "growth_factor",
                           "backoff_factor", "min_scale",
                           "max_scale"):
                    v[fk] = float(v[fk])
                if v["init_scale"] <= 0 or v["min_scale"] <= 0:
                    raise ValueError("loss scales must be > 0")
                if not (v["min_scale"] <= v["init_scale"]
                        <= v["max_scale"]):
                    raise ValueError(
                        "need min_scale <= init_scale <= max_scale")
                if v["growth_factor"] < 1.0:
                    raise ValueError("growth_factor must be >= 1")
                if not 0.0 < v["backoff_factor"] <= 1.0:
                    raise ValueError("backoff_factor must be in (0,1]")
                if v["growth_interval"] < 0:
                    raise ValueError("growth_interval must be >= 0")
        else:
            v = bool(v)
        _CONFIG[k] = v
    # capacity shrink applies immediately, not on next insert
    for cache in _CACHES.values():
        if isinstance(cache, TieredLRUCache):
            cache.trim()
    return _CONFIG


def get_config() -> Dict:
    return dict(_CONFIG)


# Named jax.checkpoint policies the remat knob accepts. Kept here (no
# jax import) so config validation, the export-cache key, and the
# autotuner knob space all agree on one list; model._checkpoint_policy
# resolves names to the jax callables at build time.
REMAT_POLICIES = (
    "nothing_saveable",
    "dots_saveable",
    "dots_with_no_batch_dims_saveable",
    "everything_saveable",
)


def _normalize_remat_policy(v):
    """None | named policy | ("save_anything_but_these_names",
    [names...]). Off-spellings (False, "off") normalize to None; a
    typo'd policy raises here, at configure time, instead of silently
    never engaging."""
    if v is None or v is False or v == "off":
        return None
    if isinstance(v, str):
        if v not in REMAT_POLICIES:
            raise ValueError(
                f"unknown remat policy {v!r}; known: "
                f"{sorted(REMAT_POLICIES)} or "
                "('save_anything_but_these_names', [names...])")
        return v
    if (isinstance(v, (tuple, list)) and len(v) == 2
            and v[0] == "save_anything_but_these_names"
            and isinstance(v[1], (tuple, list))
            and all(isinstance(n, str) for n in v[1])):
        return (v[0], tuple(v[1]))
    raise ValueError(
        f"remat policy must be None, one of {sorted(REMAT_POLICIES)}, "
        "or ('save_anything_but_these_names', [names...]); got "
        f"{v!r}")


def remat_policy():
    """Scan-level remat policy (None = off; see configure)."""
    return _CONFIG["remat_policy"]


def donation_enabled() -> bool:
    return _CONFIG["buffer_donation"]


def bn_stats_dtype():
    """BN statistics precision floor (None = at-least-fp32)."""
    return _CONFIG["bn_stats_dtype"]


def inference_quant() -> str:
    """Inference quantization mode: "off" or "int8" (see configure)."""
    return _CONFIG["inference_quant"]


def dag_auto_flops_per_op() -> float:
    """Auto-routing threshold: mean estimated FLOPs/op above which a
    DAG is compute-bound and takes the per-op walk."""
    return _CONFIG["dag_auto_flops_per_op"]


class CacheStats:
    """Counters for one executable cache.

    `retraces` counts traces actually paid (every miss that went on to
    trace, including failed traces that became negative entries);
    `trace_time_s` is the wall time those traces cost — the number to
    watch for retrace storms. `clear()`ing a cache does NOT reset its
    counters (they describe the process, not the container); use
    `reset_cache_stats()`.
    """

    __slots__ = ("name", "hits", "negative_hits", "misses",
                 "evictions_negative", "evictions_positive", "retraces",
                 "trace_time_s", "uncached_fallbacks")

    def __init__(self, name: str):
        self.name = name
        self.reset()

    def reset(self) -> None:
        self.hits = 0
        self.negative_hits = 0
        self.misses = 0
        self.evictions_negative = 0
        self.evictions_positive = 0
        self.retraces = 0
        self.trace_time_s = 0.0
        self.uncached_fallbacks = 0

    def record_trace(self, seconds: float) -> None:
        self.retraces += 1
        self.trace_time_s += seconds

    def snapshot(self) -> Dict:
        return {
            "hits": self.hits,
            "negative_hits": self.negative_hits,
            "misses": self.misses,
            "evictions": self.evictions_negative + self.evictions_positive,
            "evictions_negative": self.evictions_negative,
            "evictions_positive": self.evictions_positive,
            "retraces": self.retraces,
            "trace_time_s": round(self.trace_time_s, 6),
            "uncached_fallbacks": self.uncached_fallbacks,
        }


_MISSING = object()


class TieredLRUCache:
    """LRU cache with tiered eviction for trace executables.

    Entries matching `negative` (default: the literal `False` the DAG
    cache stores for trace-once-failed keys) form the LOW tier: they
    are never promoted on hit and are evicted before any positive
    entry — a negative entry only saves a doomed re-trace attempt,
    while a positive entry is a paid-for compiled executable.

    `capacity`/`policy` of None read the shared eager config live, so
    `device.set_dag_cache_capacity()` applies without rebuild; pass
    ints/strings for a fixed-config cache (unit tests).

    Deliberately dict-shaped (`get`/`[]=`/`del`/`len`/`clear`/`in`):
    existing callers and tests treat the DAG cache as a dict.
    """

    def __init__(self, name: str, capacity: Optional[int] = None,
                 policy: Optional[str] = None,
                 negative: Callable = lambda v: v is False,
                 stats: Optional[CacheStats] = None):
        self._od: OrderedDict = OrderedDict()
        self._neg: Dict = {}  # negative keys, insertion-ordered
        self._capacity = capacity
        self._policy = policy
        self._is_negative = negative
        self.stats = stats if stats is not None else CacheStats(name)
        self.name = name

    @property
    def capacity(self) -> int:
        return (self._capacity if self._capacity is not None
                else _CONFIG["dag_cache_capacity"])

    @property
    def policy(self) -> str:
        return (self._policy if self._policy is not None
                else _CONFIG["dag_cache_policy"])

    # -- mapping surface --------------------------------------------------
    def get(self, key, default=None):
        ent = self._od.get(key, _MISSING)
        if ent is _MISSING:
            self.stats.misses += 1
            return default
        if self._is_negative(ent):
            self.stats.negative_hits += 1
            return ent
        self.stats.hits += 1
        if self.policy == "lru":
            self._od.move_to_end(key)
        return ent

    def __setitem__(self, key, value) -> None:
        od = self._od
        if key in od:
            self._neg.pop(key, None)
            od.move_to_end(key)  # re-insert semantics for both policies
        od[key] = value
        if self._is_negative(value):
            self._neg[key] = True
        self.trim(protect=key)

    def __delitem__(self, key) -> None:
        del self._od[key]
        self._neg.pop(key, None)

    def pop(self, key, *default):
        self._neg.pop(key, None)
        return self._od.pop(key, *default)

    def __contains__(self, key) -> bool:
        return key in self._od

    def __len__(self) -> int:
        return len(self._od)

    def __iter__(self):
        return iter(self._od)

    def clear(self) -> None:
        """Drop all entries. Counters survive (see CacheStats)."""
        self._od.clear()
        self._neg.clear()

    # -- eviction ---------------------------------------------------------
    def trim(self, protect=None) -> None:
        """Evict down to capacity: oldest negative first, else oldest
        (LRU) entry. The entry being inserted (`protect`) is never the
        victim — otherwise a negative admitted to a positives-full
        cache would evict ITSELF, and the doomed trace it memoizes
        would be re-paid every step."""
        cap = self.capacity
        while len(self._od) > cap:
            victim = next((k for k in self._neg if k != protect), None)
            if victim is not None:
                del self._neg[victim]
                self._od.pop(victim, None)
                self.stats.evictions_negative += 1
                continue
            victim = next((k for k in self._od if k != protect), None)
            if victim is None:
                return  # capacity 1 holding only the protected entry
            self._od.pop(victim)
            self._neg.pop(victim, None)
            self.stats.evictions_positive += 1

    def snapshot(self) -> Dict:
        out = self.stats.snapshot()
        out["size"] = len(self._od)
        out["negative_size"] = len(self._neg)
        out["capacity"] = self.capacity
        out["policy"] = self.policy
        return out


# ---------------------------------------------------------------------------
# Registry + global counters
# ---------------------------------------------------------------------------
_CACHES: Dict[str, object] = {}  # name -> TieredLRUCache | CacheStats
_COUNTERS: Dict[str, int] = {"train_steps": 0}


def register_cache(name: str, cache) -> None:
    """Register anything with a `.snapshot() -> dict` for cache_stats()."""
    _CACHES[name] = cache


def count_train_step(n: int = 1) -> None:
    """`n` train_one_batch invocations ran (eager or graph). Lets
    observability report per-step rates (retraces/step is the
    retrace-storm smoke signal). Gradient accumulation counts its n
    microbatches in BOTH modes (eagerly via the per-microbatch
    train_one_batch calls; per graph replay via n here), so the
    counter means the same thing whichever mode trained."""
    _COUNTERS["train_steps"] += n


def grad_accum_n() -> int:
    """Configured gradient-accumulation factor (1 = off)."""
    return _CONFIG["grad_accum"]


def pipeline_microbatches():
    """Process override for every pipeline schedule's microbatch count
    (None = the stack's own setting)."""
    return _CONFIG["pipeline_microbatches"]


def moe_capacity_factor():
    """Process override for every MoE layer's capacity factor (None =
    the layer's constructor value)."""
    return _CONFIG["moe_capacity_factor"]


class _ParallelStats:
    """cache_stats()["parallel"]: the multi-axis trainer view (ISSUE
    10) — the last built pipeline's schedule geometry (stages,
    microbatches, bubble ticks and the analytic bubble fraction
    (P-1)/(M+P-1); 1F1B's combined fwd+bwd pass reports its 2(M+P-1)
    tick count), the last MoE layer's expert/capacity geometry and the
    most recent CONCRETE dropped-token fraction (graph-mode steps trace
    it into the program, so eager steps and the bench's state readback
    are the host-visible sources), and per-axis collective counts the
    parallel modules themselves emit per traced step (ppermute /
    psum / all_to_all-equivalent sharding constraints, keyed by mesh
    axis). Build notes describe live executables and survive
    reset_cache_stats(); the counters reset."""

    def __init__(self):
        self.reset()
        self.pipeline = None  # build note: {stages, microbatches, ...}
        self.moe = None       # build note: {experts, capacity, ...}

    def reset(self) -> None:
        self.pipeline_builds = 0
        self.moe_builds = 0
        self.collectives: Dict[str, Dict[str, int]] = {}
        self.dropped_frac_last = None

    def snapshot(self) -> Dict:
        return {
            "pipeline": self.pipeline,
            "moe": self.moe,
            "pipeline_builds": self.pipeline_builds,
            "moe_builds": self.moe_builds,
            "collectives": {ax: dict(kinds)
                            for ax, kinds in
                            sorted(self.collectives.items())},
            "dropped_frac_last": self.dropped_frac_last,
        }


_PARALLEL = _ParallelStats()
register_cache("parallel", _PARALLEL)


def note_pipeline_build(stages: int, microbatches: int,
                        schedule: str) -> None:
    """Record one pipeline schedule build/trace: geometry + the
    analytic bubble fraction (P-1)/(M+P-1)."""
    p, m = int(stages), int(microbatches)
    ticks = m + p - 1
    _PARALLEL.pipeline_builds += 1
    _PARALLEL.pipeline = {
        "stages": p,
        "microbatches": m,
        "schedule": schedule,
        "bubble_ticks": p - 1,
        "ticks": ticks if schedule == "gpipe" else 2 * ticks,
        "bubble_fraction": round((p - 1) / ticks, 6),
    }


def note_moe_build(experts: int, capacity: int,
                   capacity_factor: float) -> None:
    _PARALLEL.moe_builds += 1
    _PARALLEL.moe = {
        "experts": int(experts),
        "capacity": int(capacity),
        "capacity_factor": float(capacity_factor),
    }


def note_moe_dropped(frac) -> None:
    """Record a CONCRETE dropped-token fraction (eager steps / bench
    state readback; traced values never reach here)."""
    _PARALLEL.dropped_frac_last = float(frac)


def note_collective(axis: str, kind: str, n: int = 1) -> None:
    """Count collectives the parallel modules emit per traced step,
    keyed (mesh axis, kind) — e.g. ("pipe", "ppermute")."""
    d = _PARALLEL.collectives.setdefault(str(axis), {})
    d[kind] = d.get(kind, 0) + int(n)


class _AccumStats:
    """cache_stats()["accum"]: the gradient-accumulation view —
    configured n, the last built step's microbatch/effective batch
    (None until an accum step compiles or an eager accum step runs),
    and how many accumulated optimizer steps were applied. Counters
    reset with reset_cache_stats(); the build notes describe the live
    executables and survive the reset."""

    def __init__(self):
        self.accum_steps = 0
        self.last_n = None
        self.microbatch = None
        self.effective_batch = None

    def note_build(self, n: int, microbatch: int,
                   effective_batch: int) -> None:
        self.last_n = int(n)
        self.microbatch = int(microbatch)
        self.effective_batch = int(effective_batch)

    def snapshot(self) -> Dict:
        return {
            "configured_n": _CONFIG["grad_accum"],
            "n": self.last_n,
            "microbatch": self.microbatch,
            "effective_batch": self.effective_batch,
            "accum_steps": self.accum_steps,
        }

    def reset(self) -> None:
        self.accum_steps = 0


_ACCUM = _AccumStats()
register_cache("accum", _ACCUM)


def note_accum_build(n: int, microbatch: int,
                     effective_batch: int) -> None:
    """Record the microbatch geometry of an accumulation step at
    build/dispatch time (shown in cache_stats()['accum'])."""
    _ACCUM.note_build(n, microbatch, effective_batch)


def count_accum_step() -> None:
    """One ACCUMULATED optimizer step applied (n microbatches -> one
    update)."""
    _ACCUM.accum_steps += 1


class _DecodeStats:
    """cache_stats()["decode"]: the KV-cache decode view (ISSUE 16) —
    the compiled-program cache counters (`TransformerLM._gen_cache`
    routes its TieredLRUCache through `self.cache`, so hits/misses/
    evictions/retraces surface here) plus the serving tier's KV-slot
    pool: session terminals (the fourth reconciliation equation,
    sessions == completed + failed + expired + shed), per-step
    join/leave/retire traffic, streamed-token volume, and the live
    slot gauges. Counters reset with reset_cache_stats(); the slot
    gauges describe the live pool and survive the reset."""

    def __init__(self):
        self.cache = CacheStats("decode")
        self.reset()
        self.slots = 0          # gauge: pool size (0 = no pool built)
        self.slots_in_use = 0   # gauge: occupied right now
        # gauge: leaves of the live decode-params tree that are not
        # `jax.Array` (counted once at slab build). Each one is a
        # host-to-device transfer on EVERY decode-tier call; must be 0
        self.host_leaves_per_call = 0
        # gauges `cache_bytes_<kind>`: the live slab's bytes by kind, as
        # the model states them at `_build_slab` / `_grow_slab`: layers
        # that hold a ring of window positions, layers that hold the
        # whole context, layers that hold a fixed-size state (these
        # three read 0 before any slab; `_note_slab_bytes` adds a
        # further kind when a model states one: `window` and `summary`
        # are `ChunkedAttnLM`'s, `blockkey` `BlockSparseMoELM`'s)
        self.cache_bytes = {"ring": 0, "context": 0, "state": 0}

    def reset(self) -> None:
        self.cache.reset()
        self.sessions = 0       # admitted decode sessions
        self.completed = 0      # streamed every token, delivered
        self.failed = 0         # dispatch/chaos failure mid-stream
        self.expired = 0        # deadline hit mid-stream
        self.shed = 0           # refused at admission: no free slot
        self.joins = 0          # sessions entering the fused batch
        self.leaves = 0         # sessions leaving (any terminal)
        self.retires = 0        # slots freed back to the pool
        self.tokens_streamed = 0
        self.decode_steps = 0   # fused decode_step dispatches
        # those of them whose result came to the host as tokens [k, B]
        # int32 (a greedy single step or a block's steps), not logits
        self.decode_steps_tokens = 0
        # those of them dispatched behind a block whose result the host
        # had not read back yet (`ServingEngine._decode_fused_step`)
        self.decode_steps_chained = 0
        self.prefills = 0       # prefill dispatches
        # what a model's fused step counts itself, by its
        # `DecodeLM.step_counter_names`, summed over decode steps (each
        # name 0 from its model class's definition on); the names stay
        # across a reset
        self.step_counters = dict.fromkeys(
            getattr(self, "step_counters", ()), 0)
        # KV migration (ISSUE 17). `migrated` counts sessions exported
        # off this engine's books (each decrements `sessions` too, so
        # the 4-equation reconciliation stays exact per engine: the
        # session is re-admitted — and re-counted — wherever it
        # resumes); `resumed` counts sessions admitted THROUGH
        # resume_decode (KV import or ledger replay) rather than a
        # fresh submit.
        self.migrated = 0
        self.resumed = 0

    def snapshot(self) -> Dict:
        out = self.cache.snapshot()
        out.update({
            "sessions": self.sessions,
            "completed": self.completed,
            "failed": self.failed,
            "expired": self.expired,
            "shed": self.shed,
            "joins": self.joins,
            "leaves": self.leaves,
            "retires": self.retires,
            "tokens_streamed": self.tokens_streamed,
            "decode_steps": self.decode_steps,
            "decode_steps_tokens": self.decode_steps_tokens,
            "decode_steps_chained": self.decode_steps_chained,
            "prefills": self.prefills,
            **self.step_counters,
            "migrated": self.migrated,
            "resumed": self.resumed,
            "slots": self.slots,
            "slots_in_use": self.slots_in_use,
            "host_leaves_per_call": self.host_leaves_per_call,
            **{f"cache_bytes_{kind}": n
               for kind, n in self.cache_bytes.items()},
        })
        return out


_DECODE = _DecodeStats()
register_cache("decode", _DECODE)


def decode_stats() -> "_DecodeStats":
    """The live decode-tier stats object (`cache_stats()["decode"]`):
    `TransformerLM` shares its `.cache` CacheStats; the serving slot
    pool bumps the session/slot counters directly."""
    return _DECODE


def cache_stats() -> Dict:
    """Snapshot every registered cache's counters.

    Keys (per cache): hits / negative_hits / misses / evictions
    (+ negative/positive split) / retraces / trace_time_s, plus
    size/capacity/policy for bounded caches. Subsystem registrants
    ship their own counter sets — e.g. the `"slo"` entry (ISSUE 20)
    carries observed/outcomes/ticks/ingests/ingests_stale/
    alerts_emitted/collapse_events for the online SLO engine, all
    zeros-and-disabled when `device.set_slo(False)`. `train_steps`
    counts
    `Model.train_one_batch` invocations since process start (or the
    last `reset_cache_stats`), so `retraces / train_steps` after
    warmup ≈ 0 is the healthy steady state.
    """
    out = {name: c.snapshot() for name, c in sorted(_CACHES.items())}
    out["train_steps"] = _COUNTERS["train_steps"]
    return out


def reset_cache_stats() -> None:
    """Zero all counters (entries stay cached — resetting observability
    must not force retraces)."""
    for c in _CACHES.values():
        st = c.stats if isinstance(c, TieredLRUCache) else c
        if hasattr(st, "reset"):
            st.reset()
    for k in _COUNTERS:
        _COUNTERS[k] = 0


def format_stats(snapshot: Optional[Dict] = None) -> str:
    """One `cache_stats <name> k=v ...` line per cache: a stable,
    grep-able form for logs."""
    snap = cache_stats() if snapshot is None else snapshot
    lines = []
    for name, s in snap.items():
        if not isinstance(s, dict):
            continue
        kv = " ".join(f"{k}={s[k]}" for k in sorted(s))
        lines.append(f"cache_stats {name} {kv}")
    lines.append(f"cache_stats train_steps={snap.get('train_steps', 0)}")
    return "\n".join(lines)
