"""Device abstraction for the TPU-native framework.

Reference parity: SINGA's `include/singa/core/device.h` /
`src/core/device/device.cc` (`Device`, `CppCPU`, `CudaGPU`, `Platform`).
The reference routes every tensor op through
`Device::Exec(fn, read_blocks, write_blocks)`, which either runs the
lambda immediately (eager) or buffers it into a `Graph` for later
`Graph::Run()` (graph mode).

TPU-native redesign: XLA already *is* a buffering/fusing scheduler, so
`TpuDevice` does not reimplement SINGA's block-level graph. Eager ops
dispatch straight to jax (async, per-op compiled+cached by XLA); "graph
mode" is realized one level up, in `model.Model.compile(use_graph=True)`,
which traces the entire train step into a single `jax.jit` program —
the idiomatic XLA equivalent of SINGA's `Graph::Run()` replay
(SURVEY.md §1 "eager-by-default, graph-by-opt-in").

What *is* kept from the reference Device API:
  - `SetRandSeed` — counter-based RNG (threefry) replaces curand.
  - `Sync` — fences the device stream (was `cudaStreamSynchronize`).
  - `EnableGraph`/`graph_enabled` — consulted by `Model.compile`.
  - `SetVerbosity`/`PrintTimeProfiling`/`SetSkipIteration` — the per-op
    profiling table (reference: cudaEvent timing inside `Graph::Run`,
    `src/core/scheduler/scheduler.cc`); here backed by op-level wall
    timing in eager mode, and in graph (jit) mode by measured step
    times plus a per-HLO-instruction cost breakdown of the compiled
    program (`hlo_profile.py`) — fused regions are attributed back to
    framework ops via `jax.named_scope` metadata.
"""
from __future__ import annotations

import collections
import os
import time
from typing import Optional

import jax
import numpy as np

__all__ = [
    "Device",
    "CppCPU",
    "TpuDevice",
    "Platform",
    "create_cpu_device",
    "create_tpu_device",
    "create_tpu_device_on",
    "create_replica_device",
    "create_tpu_devices",
    "get_default_device",
    "enable_lazy_alloc",  # no-op parity shim
    # Eager hot-path config (singa_tpu.stats owns the state):
    "set_dag_cache_capacity",
    "set_dag_cache_policy",
    "set_buffer_donation",
    "get_eager_config",
    # Byte-diet knobs (ISSUE 2): BN statistics precision, recorded-
    # backward auto-route threshold, XLA flag profiles.
    "set_bn_stats_dtype",
    "set_dag_auto_flops_per_op",
    "set_xla_profile",
    "get_xla_profile",
    "compile_cache_dir",
    "use_compile_cache",
    "backend_initialized",
    # Int8 quantized inference (ISSUE 19): the byte-diet on the
    # decode/forward path (singa_tpu.quant reads it).
    "set_inference_quant",
    # Resilience knobs (ISSUE 3): step guard + dynamic loss scaling
    # (singa_tpu.resilience owns the state/counters).
    "set_step_guard",
    "set_loss_scaling",
    # Microbatched gradient accumulation (ISSUE 4).
    "set_grad_accum",
    # Multi-axis parallel trainer (ISSUE 10; parallel.plan owns the
    # state).
    "set_parallel_plan",
    # Scan-level rematerialization policy (ISSUE 9; singa_tpu.stats
    # owns the state, model._JitStep reads it at build time).
    "set_remat_policy",
    # Observability (ISSUE 5): span tracer + device-profiler window
    # (singa_tpu.trace owns the state).
    "set_tracing",
    # AOT export cache + shape bucketing (ISSUE 6; singa_tpu.
    # export_cache owns the state).
    "set_export_cache",
    "set_shape_buckets",
    # Continuous-batching serving tier (ISSUE 7; singa_tpu.serve owns
    # the state) + its resilience layer (ISSUE 8).
    "set_serving",
    "set_serving_resilience",
    "set_decode_serving",
    "set_fleet",
    # Migration aliases (reference names):
    "create_cuda_gpu",
    "create_cuda_gpu_on",
    "create_cuda_gpus",
]


class Device:
    """Base device. Reference: `singa::Device` (include/singa/core/device.h).

    Each instance wraps one `jax.Device` and owns a counter-based RNG
    key stream (replacing the reference's per-device curand generator).
    """

    _next_uid = 0

    def __init__(self, jax_device, lang: str):
        self.jax_device = jax_device
        self.lang = lang  # "cpp" | "tpu"  (reference: kCpp / kCuda / kOpencl)
        self.id = getattr(jax_device, "id", 0)
        self.uid = Device._next_uid
        Device._next_uid += 1
        # Commit the key to this device so every op that consumes it
        # (and therefore every random fill) executes HERE — an
        # uncommitted key would drag CPU-tensor RNG onto the default
        # accelerator.
        self._rng_key = jax.device_put(jax.random.PRNGKey(0), jax_device)
        # Graph-capture flag, consulted by Model.compile (reference:
        # Device::EnableGraph / graph_enabled_).
        self._graph_enabled = False
        # Profiling state (reference: Device::SetVerbosity /
        # PrintTimeProfiling / SetSkipIteration).
        self._verbosity = 0
        self._skip_iteration = 5
        self._op_times = collections.defaultdict(lambda: [0.0, 0])
        self._iteration = 0
        # Graph-mode profiles: label -> {"rows": [...], "step_s": float}
        # (filled by model._JitStep when verbosity > 0; see
        # hlo_profile.py for the cost model).
        self._graph_profiles = {}

    # ---- RNG ------------------------------------------------------------
    def SetRandSeed(self, seed: int) -> None:
        """Reference: `Device::SetRandSeed` (curand seed → threefry key)."""
        self._rng_key = jax.device_put(jax.random.PRNGKey(seed),
                                       self.jax_device)

    set_rand_seed = SetRandSeed

    def next_key(self):
        """Split and return a fresh PRNG key (counter-based,
        reproducible).  The split runs under compile-time eval: the
        key is host state, so even inside a trace (the eval_shape init
        forward, a jitted init) it advances CONCRETELY — a traced key
        could never be handed back to host-side consumers."""
        with jax.ensure_compile_time_eval():
            self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    # ---- Execution ------------------------------------------------------
    def put(self, array):
        """Place a host array onto this device (async)."""
        return jax.device_put(array, self.jax_device)

    def Sync(self) -> None:
        """Fence: block until all prior work on this device is done.

        Reference: `CudaGPU::Sync` → `cudaStreamSynchronize`. A bare
        device_put is NOT a fence (transfers ride a separate stream);
        instead enqueue a trivial *execution* — PJRT executes programs
        on a device in FIFO submission order — and block on its result.
        """
        x = jax.device_put(np.zeros((), np.float32), self.jax_device)
        _sync_kernel(x).block_until_ready()

    sync = Sync

    # ---- Graph-mode flag -------------------------------------------------
    def EnableGraph(self, flag: bool) -> None:
        """Reference: `Device::EnableGraph`. Consulted by Model.compile."""
        self._graph_enabled = bool(flag)

    @property
    def graph_enabled(self) -> bool:
        return self._graph_enabled

    # ---- Profiling -------------------------------------------------------
    def SetVerbosity(self, v: int) -> None:
        self._verbosity = int(v)

    def SetSkipIteration(self, k: int) -> None:
        self._skip_iteration = int(k)

    def StepIteration(self) -> None:
        self._iteration += 1

    def RecordOpTime(self, name: str, seconds: float) -> None:
        if self._verbosity > 0 and self._iteration >= self._skip_iteration:
            t = self._op_times[name]
            t[0] += seconds
            t[1] += 1

    def TimeOp(self, name: str):
        """Context manager timing one op when verbosity > 0."""
        return _OpTimer(self, name)

    def PrintTimeProfiling(self) -> str:
        """Reference: `Device::PrintTimeProfiling` — per-op time table.

        Eager ops report measured wall times; graph (jit) runs report
        the measured step time plus the compiled program's per-op XLA
        cost breakdown (hlo_profile.py)."""
        lines = ["Time Profiling:"]
        total = sum(t for t, _ in self._op_times.values())
        for name, (t, n) in sorted(
            self._op_times.items(), key=lambda kv: -kv[1][0]
        ):
            avg_us = (t / max(n, 1)) * 1e6
            pct = 100.0 * t / total if total else 0.0
            lines.append(
                f"  OP = {name:<28} Time = {avg_us:10.3f} us x {n:<6d} ({pct:5.1f}%)"
            )
        out = "\n".join(lines)
        for label, prof in self._graph_profiles.items():
            from . import hlo_profile

            out += f"\n[{label}]\n" + hlo_profile.format_table(
                prof["rows"], prof.get("step_s"))
        print(out)
        return out

    def ResetTimeProfiling(self) -> None:
        self._op_times.clear()
        self._graph_profiles.clear()
        self._iteration = 0

    # ---- Misc ------------------------------------------------------------
    def __repr__(self):
        return f"<{type(self).__name__} id={self.id} lang={self.lang}>"


@jax.jit
def _sync_kernel(x):
    return x + 1


class _OpTimer:
    def __init__(self, dev: Device, name: str):
        self.dev, self.name = dev, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dev.RecordOpTime(self.name, time.perf_counter() - self.t0)
        return False


class CppCPU(Device):
    """Host CPU device. Reference: `singa::CppCPU` (src/core/device/cpp_cpu.cc)."""

    def __init__(self, jax_device=None):
        if jax_device is None:
            # Local, not global: under multi-controller launch
            # (train_multiprocess/train_mpi), jax.devices() lists other
            # processes' devices too, and the host device must be one
            # this process can address.
            jax_device = jax.local_devices(backend="cpu")[0]
        super().__init__(jax_device, lang="cpp")


class TpuDevice(Device):
    """TPU device backed by XLA/PJRT-managed HBM buffers.

    This is the north-star component: the reference's `CudaGPU`
    (src/core/device/cuda_gpu.cc: cnmem pool + cublas/cudnn/curand
    handles + stream) re-imagined for TPU. There is no custom memory
    pool — PJRT owns HBM (SURVEY.md §7: "no custom allocator") — and no
    handle zoo — XLA compiles and caches per-op executables.
    """

    def __init__(self, jax_device):
        super().__init__(jax_device, lang="tpu")


class Platform:
    """Device discovery/factory.

    Reference: `singa::Platform` (src/core/device/platform.cc) —
    `GetNumGPUs`, `CreateCudaGPUs`, `DeviceQuery`. Here: enumerate
    PJRT devices — the TPUs, or the CPU backend's devices when the CPU
    was asked for (`_accel_devices`).
    """

    _cache: dict = {}

    @staticmethod
    def GetNumTPUs() -> int:
        try:
            return len(_backend_devices("tpu"))
        except RuntimeError:
            return 0

    # Reference-name alias so `Platform.GetNumGPUs()` keeps working.
    GetNumGPUs = GetNumTPUs

    @staticmethod
    def GetNumCPUs() -> int:
        return len(_backend_devices("cpu"))

    @staticmethod
    def CreateTpuDevices(num: int):
        devs = _accel_devices()
        if len(devs) < num:
            raise ValueError(
                f"requested {num} accelerator devices, only {len(devs)} present"
            )
        return [Platform._get(TpuDevice, d) for d in devs[:num]]

    CreateCudaGPUs = CreateTpuDevices

    @staticmethod
    def CreateTpuDeviceOn(device_id: int):
        devs = _accel_devices()
        for d in devs:
            if d.id == device_id:
                return Platform._get(TpuDevice, d)
        raise ValueError(f"no accelerator device with id {device_id}")

    @staticmethod
    def DeviceQuery(device_id: int = 0) -> str:
        devs = jax.devices()
        lines = [f"{len(devs)} device(s):"]
        for d in devs:
            lines.append(
                f"  id={d.id} platform={d.platform} kind={getattr(d, 'device_kind', '?')}"
            )
        return "\n".join(lines)

    @staticmethod
    def _get(cls, jax_device):
        key = (cls.__name__, jax_device.id, jax_device.platform)
        if key not in Platform._cache:
            Platform._cache[key] = cls(jax_device)
        return Platform._cache[key]


def _backend_devices(platform: str):
    return jax.devices(platform)


def _cpu_requested() -> bool:
    """True when the CPU was asked for on purpose: it is the FIRST
    platform jax was told to use (`JAX_PLATFORMS` / `jax.config
    jax_platforms`, e.g. "cpu"). "tpu,cpu" — the TPU with the host
    backend beside it — is a request for the TPU."""
    first = (jax.config.jax_platforms or "").split(",")[0]
    return first.strip().lower() == "cpu"


def _accel_devices():
    """The devices `TpuDevice`s are built on: the TPUs — or, ONLY when
    the CPU was asked for (`_cpu_requested`: the tier-1 tests and CPU
    mechanics runs do), the CPU backend's (possibly virtual, via
    --xla_force_host_platform_device_count) devices. There is no
    fallback: a machine whose TPU runtime failed to start must not
    train "on the TPU device" on the host."""
    if _cpu_requested():
        return jax.devices("cpu")
    try:
        return jax.devices("tpu")
    except RuntimeError as e:
        raise RuntimeError(
            f"no TPU: jax found platform {jax.default_backend()!r} "
            f"(jax_platforms={jax.config.jax_platforms!r}). singa_tpu "
            "does not fall back to the host; to run on the CPU on "
            "purpose set JAX_PLATFORMS=cpu (or jax.config.update("
            "'jax_platforms', 'cpu')) before the first jax call."
        ) from e


_default_device: Optional[Device] = None


def get_default_device() -> Device:
    """Reference: `Platform::GetDefaultDevice` — the host CppCPU."""
    global _default_device
    if _default_device is None:
        _default_device = CppCPU()
    return _default_device


def create_cpu_device() -> CppCPU:
    return get_default_device()


def create_tpu_device() -> Device:
    """The first TPU (CPU device 0 only when the CPU was asked for —
    see `_accel_devices`; no TPU otherwise is an error)."""
    return Platform._get(TpuDevice, _accel_devices()[0])


def create_tpu_device_on(device_id: int) -> Device:
    return Platform.CreateTpuDeviceOn(device_id)


def create_replica_device(index: int = 0) -> Device:
    """A PRIVATE Device object for serving replica `index` — NOT the
    `Platform._cache` singleton `create_tpu_device()` returns. A
    Device owns single-writer dispatch state (its RNG key); a fleet
    runs one dispatcher thread per replica, so replicas sharing the
    cached Device object would race it (`singa_tpu.fleet` docs the
    failure mode). Replica `index` lands on accelerator
    `index % n_devices`, so an N-chip host spreads an N-replica fleet
    one-per-chip while a 1-chip (or asked-for CPU) host stacks them
    safely."""
    devs = _accel_devices()
    return TpuDevice(devs[int(index) % len(devs)])


def create_tpu_devices(num: int):
    return Platform.CreateTpuDevices(num)


def enable_lazy_alloc(flag: bool) -> None:
    """Parity shim: reference toggles cnmem lazy allocation; PJRT owns HBM."""


# ---------------------------------------------------------------------------
# Eager hot-path config. The reference configures execution policy on
# the device layer (EnableGraph, SetVerbosity); the TPU-native eager
# cache knobs live on the same surface. State is owned by
# `singa_tpu.stats` so autograd/opt read it without an import cycle.
# ---------------------------------------------------------------------------
def set_dag_cache_capacity(n: int) -> None:
    """Max entries in the recorded-backward executable cache
    (autograd._DAG_BWD_CACHE). Shrinking evicts immediately (negative
    entries first). Default 256; size it above the working set of
    distinct DAG shapes (e.g. the number of sequence-length buckets x
    models sharing the process)."""
    from . import stats

    stats.configure(dag_cache_capacity=n)


def set_dag_cache_policy(policy: str) -> None:
    """"lru" (default: hits promote, hot executables survive cycling
    workloads) or "fifo" (insertion order only — the pre-observability
    behavior, kept for A/B measurement; see
    tests/test_cache_stats.py)."""
    from . import stats

    stats.configure(dag_cache_policy=policy)


def set_buffer_donation(flag: bool) -> None:
    """Donate param/momentum/grad buffers into the jitted optimizer
    update and the graph-mode step (default on). Read at executable
    build time: an already-compiled graph-mode step keeps its donation
    contract until the model is re-compile()d."""
    from . import stats

    stats.configure(buffer_donation=flag)


def get_eager_config() -> dict:
    """Snapshot of the eager hot-path config knobs."""
    from . import stats

    return stats.get_config()


def set_bn_stats_dtype(dt) -> None:
    """BatchNorm statistics precision floor (byte-diet knob).

    None (default): batch mean/var and the normalization math run in
    at-least-fp32 — under bf16 AMP this materializes an fp32 copy of
    the activations that round-trips HBM (the reference-parity
    behavior). "bfloat16" / "float16": the floor drops, so bf16
    activations are normalized in bf16 and the fp32 round-trip
    disappears. Inputs are never DOWNcast: fp32 activations keep fp32
    statistics under any floor, and the f64 gradient-audit path is
    untouched. Read at op-dispatch / trace time — recompile graph-mode
    models (and the recorded-backward cache keys on it) after
    toggling."""
    from . import stats

    stats.configure(bn_stats_dtype=dt)


def set_inference_quant(mode: str) -> None:
    """Post-training quantization for the INFERENCE stack (ISSUE 19).

    "off" (default): fp32 decode/forward. "int8": decode-tier params
    become symmetric per-channel int8 with dequant-at-use and fp32
    accumulation, the serving KV slab becomes int8 payload + separate
    f32 scale planes, and forward executables stream int8 param
    payloads (singa_tpu.quant). Training paths ignore the knob;
    `generate()` stays fp32 — quant covers `decode_step`/`decode_scan`
    /`prefill_slab` and the ServingEngine forward path. Read at
    decode-program build time and part of
    `export_cache.knob_fingerprint()`: flipping it is an AOT-store
    miss, never a stale load. Serving engines size their slab at
    `warm_decode()` — arm the knob BEFORE building the engine."""
    from . import stats

    stats.configure(inference_quant=mode)


def set_step_guard(flag: bool) -> None:
    """Fold an all-finite check on loss + gradients into the compiled
    train step (default off). A non-finite step leaves params and
    optimizer slots bit-identical to their pre-step values via
    on-device selects — no host round-trip on the hot path — and
    increments the counters in `cache_stats()["resilience"]`. On a
    device mesh the finite bit is reduced over the global gradients
    inside the one SPMD program, so every rank skips identically.
    Read at executable build time: re-`compile()` an already-compiled
    graph-mode model after toggling (same contract as
    `set_buffer_donation`)."""
    from . import stats

    stats.configure(step_guard=flag)


def set_loss_scaling(init_scale=2.0 ** 15, growth_factor: float = 2.0,
                     backoff_factor: float = 0.5,
                     growth_interval: int = 2000,
                     min_scale: float = 1.0,
                     max_scale: float = 2.0 ** 24) -> None:
    """Dynamic loss scaling for the AMP path (implies the step guard).

    The backward seed is multiplied by a running scale; gradients are
    unscaled inside the fused/jitted update. After `growth_interval`
    consecutive finite steps the scale grows ×`growth_factor` (capped
    at `max_scale` — an uncapped scale overflows to inf under all-zero
    grads and backoff could never recover); an overflowed (non-finite)
    step skips the update and backs the scale off ×`backoff_factor`
    (floored at `min_scale`). Keep the factors powers of two and the
    scale/unscale round trip is bit-exact. `set_loss_scaling(None)`
    disables. Resets the live scale state; re-`compile()` graph-mode
    models after toggling."""
    from . import resilience, stats

    if init_scale is None:
        stats.configure(loss_scaling=None)
    else:
        stats.configure(loss_scaling={
            "init_scale": init_scale,
            "growth_factor": growth_factor,
            "backoff_factor": backoff_factor,
            "growth_interval": growth_interval,
            "min_scale": min_scale,
            "max_scale": max_scale,
        })
    resilience.reset_state()


def set_grad_accum(n: int) -> None:
    """Microbatched gradient accumulation factor (default 1 = off).

    With n > 1 the compiled train step reshapes its incoming batch to
    `[n, batch/n, ...]` and runs a `lax.scan` over the microbatches
    INSIDE the one XLA program — forward + backward per microbatch,
    gradients accumulated in fp32 — applying the optimizer exactly
    once on the mean at the end. Train at an effective batch n× what
    fits HBM (the live activation/gradient footprint stays at
    microbatch size), and on a device mesh the gradient reduction
    fires once per accumulated step instead of once per microbatch.
    The eager path microbatches the same way with one fused optimizer
    dispatch. The StepGuard finite check / DynamicLossScaler unscale
    run once on the ACCUMULATED gradients, and bf16 slot storage
    quantizes once at the final apply.

    Read at executable build time (same contract as
    `set_buffer_donation`/`set_step_guard`): re-`compile()` an
    already-compiled graph-mode model after toggling.
    `Model.compile(..., grad_accum=n)` overrides per-model. Batch
    sizes must divide by n (`singa_tpu.data.microbatches` is the
    feeding-side splitter). Geometry + applied-step counters surface
    in `cache_stats()["accum"]`."""
    from . import stats

    stats.configure(grad_accum=n)


def set_parallel_plan(plan=None, **axes) -> None:
    """Process-default `parallel.ParallelPlan` (ISSUE 10): the
    multi-axis geometry `Model.compile` adopts when called without
    `mesh`/`plan`. Pass a plan object, axis sizes
    (`set_parallel_plan(data=4, pipe=2)` builds one — extra keywords
    `pipeline_microbatches`/`pipeline_schedule`/`moe_capacity_factor`
    carry the policy), or nothing to clear. With a plan armed, a bare
    `compile(..., use_graph=True)` trains as one SPMD program over
    the plan's mesh: tensor-parallel layers under the GSPMD rules,
    `PipelineStack` stages on the "pipe" axis (1F1B schedule),
    `MoE` experts on the "expert" axis — composed with grad-accum,
    the step guard, and the loss scaler exactly like the DP path.
    Read at compile time: re-`compile()` after toggling (the
    `set_grad_accum` contract). Counters:
    `cache_stats()["parallel"]`."""
    from .parallel import plan as plan_mod

    if plan is not None and axes:
        raise ValueError(
            "set_parallel_plan: pass a ParallelPlan OR axis sizes, "
            "not both")
    if plan is None and axes:
        plan = plan_mod.ParallelPlan(**axes)
    plan_mod.set_process_plan(plan)


def set_remat_policy(policy, *names) -> None:
    """Scan-level rematerialization policy for the compiled train step
    (ISSUE 9; ROADMAP item 2's byte lever, searchable by the
    autotuner). None (default) = off; a named `jax.checkpoint` policy —
    "dots_saveable" (matmul/conv-free recompute: dot results stay
    saved, everything else is recomputed in the backward),
    "nothing_saveable" (maximum recompute: only region inputs
    survive), "dots_with_no_batch_dims_saveable",
    "everything_saveable" — or
    `set_remat_policy("save_anything_but_these_names", "a", "b")` for
    the name-keyed policy (pairs with `jax.ad_checkpoint.checkpoint_name`
    inside custom models).

    With a policy armed, the graph-mode step wraps each microbatch's
    ENTIRE forward+loss region in `jax.checkpoint(policy=...)` and
    derives its gradients from one `jax.vjp` over that region — inside
    `_JitStep._accum_step`'s `lax.scan` when gradient accumulation is
    on (fp32 accumulation preserved, the optimizer still applies once
    on the mean), and as a single whole-batch region when accumulation
    is off. Activation memory across the fwd→bwd boundary drops to the
    policy's saveable set; the recompute FLOPs are the price
    (μ-cuDNN's memory/recompute trade, arXiv:1804.04806). The effect
    is CPU-verifiable via `hlo_profile.peak_bytes_estimate` on
    `Model.step_hlo_text`. Composes with the per-op
    `autograd.set_remat` (which checkpoints individual op fns) and
    joins the export-cache knob fingerprint, so AOT artifacts can
    never go stale across a policy flip. Eager mode ignores the
    policy. Read at executable build time (the
    `set_buffer_donation`/`set_grad_accum` contract): re-`compile()`
    an already-compiled graph-mode model after toggling. Requires an
    optimizer on the model and `train_one_batch` to call
    `backward_and_update` exactly once (the grad-accum contract)."""
    from . import stats

    if names:
        policy = (policy, list(names))
    stats.configure(remat_policy=policy)


def set_tracing(flag: bool = True, ring_capacity: Optional[int] = None,
                ship_capacity: Optional[int] = None) -> None:
    """Toggle the span-based host tracer (`singa_tpu.trace`).

    Disabled (the default) the tracer is a strict no-op — `span()`
    hands back a shared null context, nothing is recorded. Enabled,
    spans land in a bounded ring buffer: the step path is pre-wired
    (`BatchIter` data-wait, eager `train_one_batch` + fused optimizer
    apply, graph-step dispatch vs `block_until_ready` device-sync,
    sharded placement, resumable-loop checkpoint save/restore), so a
    training loop wrapped in `trace.step_span(i)` decomposes each
    step for `trace.export_chrome_trace(path)` (Perfetto-loadable),
    `trace.format_summary()`, and the `MetricsLogger` per-step JSONL.
    The serving/fleet request path is pre-wired too: every fleet
    request gets a trace context (`trace_id`) threaded through
    routing, failover, the IPC boundary, and the worker dispatch —
    `trace.merge_chrome_traces` folds N processes' spans into one
    aligned timeline (see README "Fleet observability").
    NOTE: enabling adds no device sync: a graph-mode step is never
    fenced, and `device_sync` is the span around a loop's own loss
    read (`run_resumable`), where it waits anyway. Enabled spans are
    also `jax.profiler.TraceAnnotation`s ("singa:<name>"): any profiler
    session (`jax.profiler.start_trace`) shows them on the host's
    threads under the device's operations; the compiled step's phases
    (`step.call`, `step.place`, `step.enqueue`, `step.bind`:
    `trace.phase`) are such annotations with tracing off too. `ring_capacity` resizes the
    span ring (default 16384 spans); `ship_capacity` bounds the cross-process span ship-back buffer a
    fleet WORKER drains into reply/heartbeat frames (0 = off, the
    default — overflow drops oldest, counted `ship_dropped`).
    Counters: `cache_stats()["trace"]`."""
    from . import trace

    trace.configure(enabled=flag, ring_capacity=ring_capacity,
                    ship_capacity=ship_capacity)


def set_export_cache(directory) -> None:
    """Arm the persistent AOT executable store (`singa_tpu.
    export_cache`): graph-mode train steps, sharded mesh steps, and
    forward executables are serialized with `jax.export` into
    `directory`, keyed by (model topology fingerprint, abstract shape
    signature, dtype, device kind, and a snapshot of every
    step-affecting knob), and a process that finds a matching artifact
    DESERIALIZES it instead of re-tracing — millisecond warm starts
    where tracing took seconds. A knob/topology change changes the
    key, so a stale artifact can never load; a corrupt artifact falls
    back to tracing loudly (`tools/export_cache_gc.py` lists/validates/
    collects the store). NOTE: export-cached steps run without buffer
    donation (see `_JitStep._build`). `None` disables. Counters:
    `cache_stats()["export"]`."""
    from . import export_cache

    export_cache.configure(directory=directory)


def set_shape_buckets(max_batch=None, seq_dim=None, max_seq=None) -> None:
    """Arm the powers-of-two shape-bucketing policy: forward/serving
    dispatches pad their batch dim (and `seq_dim`, when given — right
    padding, causal-attention-safe only) up to the next pow2 bucket
    and slice padded rows back off the outputs, so diverse traffic
    retraces at most once per bucket instead of once per novel shape
    — and fills at most that many export-cache artifacts. A shape
    above `max_batch`/`max_seq` raises `export_cache.
    BucketOverflowError` (loud, never a silent retrace). Ceilings
    must be powers of two. `set_shape_buckets()` with no args
    disables. Works with or without `set_export_cache`."""
    from . import export_cache

    if max_batch is None and max_seq is None and seq_dim is None:
        export_cache.configure(buckets=None)
    else:
        # seq_dim without max_seq falls through to BucketPolicy's own
        # "seq_dim set but max_seq missing" ValueError — silently
        # disabling a policy the caller thought they armed would leave
        # retraces unbounded with no signal.
        export_cache.configure(buckets=export_cache.BucketPolicy(
            max_batch=max_batch if max_batch is not None else 4096,
            seq_dim=seq_dim, max_seq=max_seq))


def set_serving(max_batch=None, max_wait_ms=None,
                max_queue=None) -> None:
    """Process defaults for the continuous-batching serving tier
    (`singa_tpu.serve.ServingEngine`): `max_batch` bounds the rows one
    fused dispatch coalesces, `max_wait_ms` is how long the dispatcher
    holds the FIRST queued request waiting for companions (the
    latency floor a lone request pays for batch occupancy), and
    `max_queue` bounds the admission queue (full ⇒ a loud
    `ServeQueueFullError` drop, counted in `cache_stats()["serve"]` —
    never an unbounded backlog). Engines constructed afterwards read
    these; per-engine constructor args override. Only the arguments
    given change."""
    from . import serve

    kw = {}
    if max_batch is not None:
        kw["max_batch"] = max_batch
    if max_wait_ms is not None:
        kw["max_wait_ms"] = max_wait_ms
    if max_queue is not None:
        kw["max_queue"] = max_queue
    if kw:
        serve.configure(**kw)


def set_serving_resilience(**kw) -> None:
    """Process defaults for the serving-tier resilience layer
    (`singa_tpu.serve.ServingEngine`; ISSUE 8). Only the keys given
    change; engines constructed afterwards read them (constructor
    args override per-engine). Keys:

      deadline_ms       default per-request deadline: still queued
                        past it ⇒ the future fails with
                        `ServeDeadlineError` BEFORE batch assembly
                        (counted `expired`); expired mid-dispatch ⇒
                        delivered but counted `late` with
                        `reply.deadline_exceeded=True`. None = off.
      max_retries       failed fused dispatches retry the whole group
                        this many times with exponential backoff
                        before bisecting to isolate poison requests.
      backoff_ms        base retry backoff (doubles per attempt).
      backoff_jitter    ± fraction of deterministic seed-keyed jitter.
      shed_watermark    queue depth at/above which NEW requests shed
                        with `ServeOverloadError` (carries
                        `retry_after_ms`). None = hard drop only.
      adaptive_wait     shrink the coalesce window toward 0 under
                        sustained queue depth (latency degrades
                        before availability).
      max_restarts      supervised dispatcher restarts before the
                        engine gives up and fails the queue.
      drain_timeout_s   `stop(drain=True)` bound: past it, remaining
                        futures fail with `ServeClosedError` instead
                        of the stop hanging on a dead dispatch.
      unhealthy_failures  consecutive dispatch-failure streak at
                        which `health()` turns unhealthy.
      health_file       JSON health-snapshot path probed by
                        `tools/serve_health.py` (exit code 0/1/2 =
                        ready/degraded/unhealthy). None = off.

    Counters: `cache_stats()["serve"]` (expired/late/shed/failed/
    poisoned/retries/dispatch_failures/restarts)."""
    from . import serve

    if kw:
        serve.configure_resilience(**kw)


def set_decode_serving(max_sessions=None, max_new_tokens=None,
                       prefill_batch=None, decode_block=None) -> None:
    """Process defaults for the KV-cached decode tier
    (`ServingEngine.submit_decode`; ISSUE 16): `max_sessions` sizes
    the KV-slot pool — the admission-control bound on concurrent
    generative sessions (queued + live; no free slot ⇒ a loud
    `ServeOverloadError` with `retry_after_ms`, counted `shed` in
    `cache_stats()["decode"]`); `max_new_tokens` caps the per-session
    generation length a submit may request; `prefill_batch` bounds how
    many new sessions prefill per dispatcher cycle (the prefill/decode
    split — long prompts never stall the fused decode batch by more
    than this); `decode_block` caps the greedy run-ahead — how many
    fused steps may dispatch as one scanned program when no session
    joins, leaves, expires, or samples inside the block (1 = every
    token its own dispatch). Engines constructed afterwards read
    these; per-engine constructor args override. Only the arguments
    given change."""
    from . import serve

    kw = {}
    if max_sessions is not None:
        kw["max_sessions"] = max_sessions
    if max_new_tokens is not None:
        kw["max_new_tokens"] = max_new_tokens
    if prefill_batch is not None:
        kw["prefill_batch"] = prefill_batch
    if decode_block is not None:
        kw["decode_block"] = decode_block
    if kw:
        serve.configure_decode(**kw)


def set_fleet(**kw) -> None:
    """Process defaults for the fleet serving tier
    (`singa_tpu.fleet.FleetRouter`; ISSUE 11). Only the keys given
    change; routers constructed afterwards read them (constructor
    args override per-router). Keys:

      max_failover_hops     re-submits of one request to DIFFERENT
                            replicas after a replica fails it
                            (`ServeDispatchError` / replica death).
                            Poison verdicts (`ServePoisonedError`)
                            never fail over. 0 = single-engine
                            semantics.
      max_shed_retries      rounds of honoring the smallest
                            `retry_after_ms` (seed-jittered) when
                            EVERY replica in rotation sheds; trying a
                            different replica costs no wait and
                            always comes first.
      max_shed_sleep_s      cap on one shed wait.
      health_max_age_s      health-snapshot age beyond which a
                            replica is ejected as stale (a wedged
                            writer stops refreshing; fail closed).
      probe_backoff_ms      base backoff between rejoin probes of an
                            ejected replica (doubles per failed
                            probe, seed-jittered).
      max_restarts          supervisor restarts per dead replica
                            before it is abandoned ("failed").
      supervise_interval_s  supervisor sweep period (restart/rejoin
                            latency floor).
      metrics_every         fleet metrics JSONL record every N routed
                            requests (transitions always log).

    Multi-process transport keys (ISSUE 13; `singa_tpu.fleet_proc`):

      transport             "engine" (in-process replicas), "proc"
                            (worker subprocesses behind the same
                            Replica protocol), or "tcp" (ISSUE 18:
                            listen-mode workers over a routable TCP
                            socket with generation fencing +
                            per-frame sequence numbers) — what
                            `fleet.make_replicas` builds.
      ipc_deadline_ms       per-message IPC bound: a missing admission
                            ACK (or a reply this far past the
                            request's own deadline) fails the caller
                            with a structured `ProcTransportError`
                            (`ServeDispatchError` subclass ⇒ the
                            router fails over unchanged).
      heartbeat_interval_s  worker heartbeat period; a missed
                            heartbeat ages the health snapshot into
                            the router's stale ejection (fail
                            closed). Keep `health_max_age_s` a few
                            multiples above it.
      spawn_timeout_s       bound on worker spawn → HELLO (shared by
                            the supervisor respawn path).
      max_inflight          in-flight requests per worker before the
                            parent sheds with `retry_after_ms`
                            instead of ballooning the pipe.

    TCP transport keys (ISSUE 18; modes listen/connect):

      reconnect_window_s    after a socket EOF/corruption in a TCP
                            mode, how long the parent holds the
                            worker's generation open for a
                            fence-checked reconnect before declaring
                            it dead (in-flight requests fail over
                            immediately; new submits shed with
                            `retry_after_ms` during the window).
      max_frame_bytes       reader-side bound on one frame's payload
                            (>= 1024): a hostile/corrupt length
                            prefix fails the connection with
                            `FrameCorruptError` instead of ballooning
                            RSS.

    Counters: `cache_stats()["fleet"]` (routed/failovers/refused/
    rejected, ejections/rejoins/restarts, per-replica state incl.
    transport ledgers)."""
    from . import fleet

    if kw:
        fleet.configure(**kw)


def set_slo(enabled: bool = True, **kw) -> None:
    """Arm (or disarm) the online SLO engine (`singa_tpu.slo`;
    ISSUE 20): mergeable streaming quantile sketches over the serving
    segments (queue_wait/ipc/dispatch/reply/ttft/tpot), multi-window
    burn-rate alerting over a declarative `SLOSpec`, and per-replica
    anomaly detection.  `set_slo(True, ...)` builds a FRESH engine —
    sketches, windows, and alert state start empty (documented reset
    semantics).  When disabled, every feed site is a strict no-op
    (zero allocation) and worker heartbeats carry no `slo` key at
    all.  Keys:

      rel_err            sketch relative-error bound (default 0.02):
                         any reported quantile is within this
                         relative distance of the true sample
                         quantile. Smaller = more buckets used.
      max_buckets        live-bucket budget per sketch (default 512);
                         overflow collapses the LOW tail upward,
                         counted loudly (`collapsed`), never the high
                         quantiles operators page on.
      window_scale       multiplies the canonical Google-SRE burn
                         windows (fast 1h/5m at burn 14.4 => page;
                         slow 3d/6h at burn 1.0 => ticket) down to
                         bench timescales. 1.0 = production windows.
      spec               {"availability": target,
                          "latency": {segment: {"threshold_ms": ...,
                                      "target": ...}}} — the SLO
                         itself. Latency objectives are request-based
                         (fraction of samples under the threshold).
      alerts_path        JSONL stream for alert state transitions
                         (schema-stable records; every transition of
                         pending -> firing -> resolved is one line).
      hb_gap_mult /      heartbeat-gap anomaly: breach when the gap
      hb_gap_min_s       exceeds max(min_s, mult * EWMA baseline).
      clock_mult /       clock anomaly: |offset_us| beyond the
      clock_slack_us     transport estimator's own uncertainty_us *
                         mult + slack.
      spike_window_s /   counter-rate anomaly: windowed counter delta
      spike_mult         vs max(per-counter floor, mult * EWMA).
      anomaly_pending_s/ holds before an anomaly fires / resolves
      anomaly_resolve_s  (flap suppression).

    Reads: `fleet.FleetRouter.slo_report()` (fleet-merged),
    `serve` health snapshots gain an `alerts` block, and
    `cache_stats()["slo"]` counts feeds/ingests/ticks/alerts."""
    from . import slo

    slo.configure(enabled, **kw)


def set_dag_auto_flops_per_op(v: float) -> None:
    """Recorded-backward auto-routing threshold (FLOPs/op): under
    `autograd.set_dag_backward("auto")` (the default), DAGs whose
    estimated mean backward FLOPs per op exceed this take the per-op
    walk (compute-bound: dispatch overhead is noise), the rest take
    the one-dispatch recorded replay. Routing decisions are surfaced
    in `cache_stats()['dag_route']`."""
    from . import stats

    stats.configure(dag_auto_flops_per_op=v)


# ---------------------------------------------------------------------------
# XLA flag profiles. The `--xla_tpu_*` flags belong to libtpu, which
# reads them from LIBTPU_INIT_ARGS when the TPU client is created;
# jaxlib's own parser does not know them, and one of them in XLA_FLAGS
# aborts the process at backend start ("Unknown flags in XLA_FLAGS";
# chip run, PR 21). So profiles go into LIBTPU_INIT_ARGS, and must be
# applied before the first jax.devices() / computation of the process:
# first thing in a process is the supported path.
# ---------------------------------------------------------------------------
_XLA_PROFILES = {
    # no-op baseline: whatever the environment already set
    "default": (),
    # The latency-hiding/fusion set used for bench runs (BASELINE.md
    # roofline: un-overlapped epilogues are part of the residual gap).
    # Scheduler overlaps collective/async work with compute; the
    # async-collective fusion flags let it move allgathers off the
    # critical path on meshed steps.
    "latency": (
        "--xla_tpu_enable_latency_hiding_scheduler=true",
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
        "--xla_tpu_overlap_compute_collective_tc=true",
    ),
}
_xla_profile_applied: Optional[str] = None


def backend_initialized() -> bool:
    """Whether this process has created a jax backend — and so, on a
    TPU machine, holds the chip (one process per chip). Asking does
    not create one."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def set_xla_profile(name: str = "latency"):
    """Apply a named XLA flag profile by merging it into
    LIBTPU_INIT_ARGS.

    Returns the list of flags applied. Idempotent: re-applying a
    profile (or switching profiles) first strips every flag any
    profile here owns, so flags never duplicate or linger. Flags are
    consumed at backend init — if a jax backend already exists in this
    process, a warning is printed and the profile only affects
    backends created afterwards (apply it before touching jax, which
    is the supported path)."""
    global _xla_profile_applied
    if name not in _XLA_PROFILES:
        raise ValueError(
            f"unknown XLA profile {name!r}; known: "
            f"{sorted(_XLA_PROFILES)}")
    owned = {f.split("=")[0] for flags in _XLA_PROFILES.values()
             for f in flags}
    current = [f for f in os.environ.get("LIBTPU_INIT_ARGS", "").split()
               if f.split("=")[0] not in owned]
    flags = list(_XLA_PROFILES[name])
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(current + flags).strip()
    _xla_profile_applied = name
    if backend_initialized():
        import sys

        print("singa_tpu: set_xla_profile applied after backend "
              "init; flags only affect backends created later",
              file=sys.stderr)
    return flags


def get_xla_profile() -> Optional[str]:
    """Name of the profile applied by set_xla_profile (None if never
    called in this process)."""
    return _xla_profile_applied


# ---------------------------------------------------------------------------
# Persistent compile cache: ONE rule for where it lives, called by every
# entry point that compiles on the chip (chip_smoke.py,
# perfbench/run.py, examples/cnn/benchmark.py). The directory is part of what makes a
# later run hit, so it is never a temporary name, a pid or a time.
# ---------------------------------------------------------------------------
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives:
    `JAX_COMPILATION_CACHE_DIR` if it is set, else
    `<checkout>/.jax_cache` (git-ignored)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on jax's persistent compilation cache at
    `compile_cache_dir()` and return that directory. If
    `JAX_COMPILATION_CACHE_DIR` is set, jax's own reading of it stands
    and no directory is set in code. Call before the first compilation
    of the process."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # jax leaves an instruction's metadata out of the cache's key by
    # default, so a cache that another tree warmed hands back that
    # tree's `op_name`s: a step whose scopes the device trace is joined
    # to (hlo_profile.scope_map) would read an older program's, or none.
    # With the metadata in the key, a location is the traced line alone
    # and not the ten frames of call stack above it, or the same program
    # would be compiled again for every script that reaches it.
    # (`jax_include_full_tracebacks_in_locations=False` would do that
    # too, and drops most `op_name`s with the frames in this jax.)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    return path


# ---------------------------------------------------------------------------
# Migration aliases: the reference's Python API spells these
# `device.create_cuda_gpu*` (python/singa/device.py). Keep the names so
# reference user code ports by import-swap; they build TPU devices here.
# ---------------------------------------------------------------------------
create_cuda_gpu = create_tpu_device
create_cuda_gpu_on = create_tpu_device_on
create_cuda_gpus = create_tpu_devices
