"""Cost-model-guided autotuner over the step knob space (ISSUE 9;
ROADMAP items 2 + 5).

The knob space — slot dtype x BN-stats dtype x XLA profile x accum
geometry x scan-level remat policy x Pallas block shapes — outgrew
hand-queued bench matrix rows. TVM (arXiv:1802.04799) shows a
cost-model-guided search over exactly this kind of configuration space
beats hand tuning *when candidates can be scored cheaply*; μ-cuDNN
(arXiv:1804.04806) is the precedent for making the memory/recompute
trade (the remat knob) part of that search. Here the cheap scorer is
the CPU-side HLO meter from PR 2:

  * step HBM bytes       — `hlo_profile.bytes_accessed` over the
                           optimized whole-step HLO
                           (`Model.step_hlo_text`),
  * analytic FLOPs       — `hlo_profile.profile_hlo` row sums,
  * peak live bytes      — `hlo_profile.peak_bytes_estimate` over the
                           PRE-optimization HLO (where the remat
                           policy's checkpoint barriers still stand),

combined by a roofline cost model per device kind:

    est. step time = max(bytes / HBM_bandwidth, flops / peak_flops)
    score          = effective_batch / est. step time   (examples/s)

subject to peak_bytes <= the chip's HBM capacity — which is how the
remat knob earns its seat: it never wins the pure roofline (recompute
adds bytes AND flops) but it turns infeasible accum/batch geometries
feasible. The whole search runs on CPU in CI; chip runs only
CONFIRM the frontier, never explore it.

Measured scores outrank modeled ones (the TVM lesson): per-config
JSONL from `benchmarks/pallas_tune.py --cpu --jsonl` feeds the Pallas
block-shape axis, and any metrics JSONL whose records carry a
`config` dict (the autotuner's own search log qualifies) overrides
the model for exact config matches.

Search is DETERMINISTIC: proposals come from a seeded
`random.Random`, scoring is pure given the model topology, and the
winner tie-breaks on (score, fewest non-default knobs, canonical
JSON) — the same seed always reproduces the same winner. No
wall-clock enters proposals.

The best-known config persists per (model topology fingerprint, chip
kind) in a JSON store (`TunedStore`) that the serving tier
(`serve.ServingEngine`) loads by default; the store also
carries name aliases ("resnet") so callers can resolve a config
before the model's params exist.

Counters: `cache_stats()["tuning"]`.
"""
from __future__ import annotations

import hashlib
import json
import os
import random
import time
from typing import Callable, Dict, List, Optional, Tuple

from . import stats as stats_mod

__all__ = [
    "KNOBS",
    "HLO_KNOBS",
    "CHIP_SPECS",
    "normalize_chip",
    "default_config",
    "validate_config",
    "canonical",
    "CostModelScorer",
    "propose",
    "autotune",
    "TunedStore",
    "default_store_path",
    "apply_config",
    "load_best",
    "apply_best_for_serving",
    "ingest_pallas_jsonl",
    "ingest_metrics_jsonl",
    "MeasuredScores",
]


# ---------------------------------------------------------------------------
# Knob space. Values are ORDERED (the proposal enumeration and the
# deterministic tie-break both read this order); the first value of
# every knob is its process default.
# ---------------------------------------------------------------------------
KNOBS: Dict[str, tuple] = {
    # AMP compute dtype (tensor.set_compute_dtype) — the headline
    # bench axis: the byte-diet knobs below only pay off under it
    # (fp32 activations keep fp32 stats and slots convert at fusion
    # boundaries; see tests/test_byte_diet.py)
    "compute_dtype": (None, "bfloat16"),
    # optimizer-slot storage dtype (opt.Optimizer.set_slot_dtype;
    # fp32 master math either way)
    "slot_dtype": (None, "bfloat16", "float16"),
    # BatchNorm statistics precision floor (device.set_bn_stats_dtype)
    "bn_stats_dtype": (None, "bfloat16", "float16"),
    # XLA flag profile (device.set_xla_profile) — cost-model-NEUTRAL
    # (flags change scheduling, not bytes/flops): only a measured
    # score can promote "latency", so the model never hallucinates a
    # win it cannot see.
    "xla_profile": ("default", "latency"),
    # microbatched gradient accumulation (device.set_grad_accum)
    "grad_accum": (1, 2, 4),
    # scan-level rematerialization policy (device.set_remat_policy) —
    # the headline new knob: searchable memory/recompute trade
    "remat_policy": (None, "dots_saveable", "nothing_saveable"),
    # Multi-axis parallel trainer knobs (ISSUE 10; ROADMAP item 3).
    # mesh_geometry: a ParallelPlan axis spec ("data=4,pipe=2") the
    # scorer compiles the step over (None = single device). Values
    # whose axis product does not divide the process's device count
    # score infeasible (loud row reason) rather than erroring — the
    # same knob space serves 1-device CI and the 8-device mesh.
    "mesh_geometry": (None, "data=4,pipe=2", "data=4,model=2",
                      "data=2,model=2,pipe=2", "data=4,expert=2"),
    # pipeline_microbatches: every PipelineStack's microbatch count
    # (None = pipe size); more microbatches shrink the bubble
    # (P-1)/(M+P-1) but shrink per-tick MXU shapes.
    "pipeline_microbatches": (None, 2, 4, 8),
    # moe_capacity_factor: every MoE layer's expert capacity factor
    # (None = the layer/plan setting); higher drops fewer tokens but
    # pads more expert compute.
    "moe_capacity_factor": (None, 1.0, 1.25, 1.5, 2.0),
    # Int8 quantized inference (ISSUE 19; device.set_inference_quant):
    # the byte-diet on the decode/forward path — int8 param payloads
    # + packed KV slab with dequant-at-use. Inference-only (training
    # steps ignore it); the serving score path + measured records are
    # how it earns trust (the TVM lesson), not the analytic model.
    "inference_quant": ("off", "int8"),
    # Pallas kernel block shapes (env-overridable at
    # ops/pallas_kernels import; benchmarks/pallas_tune.py sweeps
    # them). Cost-model-neutral on CPU — they join the search through
    # measured sweep JSONL (`ingest_pallas_jsonl`).
    "pallas_attn_tq": (None, 64, 128, 256, 512),
    "pallas_row_budget": (None, 1 << 17, 1 << 18, 1 << 19, 1 << 20,
                          1 << 21),
    "pallas_hist_budget": (None, 1 << 11, 1 << 12, 1 << 13, 1 << 14,
                           1 << 15),
}

# The subset whose values change the traced/compiled step HLO — the
# score cache keys on exactly these (xla/pallas knobs are neutral to
# the HLO meter, so configs differing only there share a measurement).
HLO_KNOBS = ("compute_dtype", "slot_dtype", "bn_stats_dtype",
             "grad_accum", "remat_policy", "mesh_geometry",
             "pipeline_microbatches", "moe_capacity_factor",
             "inference_quant")

# Pallas knob -> the env var pallas_kernels reads at import, and the
# module global it reads into (apply_config pokes the live module too
# — by apply time ops/pallas_kernels has usually ALREADY been
# imported, so the env var alone would be a silent no-op in-process;
# the kernels re-read the globals at trace time, so later traces pick
# the new blocks up).
PALLAS_ENV = {
    "pallas_attn_tq": "SINGA_TPU_ATTN_TQ",
    "pallas_row_budget": "SINGA_TPU_ROW_BUDGET",
    "pallas_hist_budget": "SINGA_TPU_HIST_BUDGET",
}
PALLAS_ATTR = {
    "pallas_attn_tq": "_ATTN_TQ",
    "pallas_row_budget": "_ROW_BUDGET",
    "pallas_hist_budget": "_HIST_BUDGET",
}


# ---------------------------------------------------------------------------
# Device roofline specs. Bandwidth/peak per chip kind (BASELINE.md pins
# the v5e at ~819 GB/s / 197 bf16 TFLOP/s; the others from published
# TPU system specs). The "cpu" row exists so the search smoke runs
# chip-agnostic in CI — its numbers model a commodity host, and the
# RELATIVE ranking (which is all a search needs) is bandwidth-bound
# like the TPU rows.
# ---------------------------------------------------------------------------
CHIP_SPECS: Dict[str, Dict] = {
    "v5e": {"hbm_gbps": 819.0, "peak_flops": 197e12,
            "hbm_bytes": 16e9},
    "v5p": {"hbm_gbps": 2765.0, "peak_flops": 459e12,
            "hbm_bytes": 95e9},
    "v4": {"hbm_gbps": 1228.0, "peak_flops": 275e12,
           "hbm_bytes": 32e9},
    "v6e": {"hbm_gbps": 1640.0, "peak_flops": 918e12,
            "hbm_bytes": 32e9},
    "cpu": {"hbm_gbps": 50.0, "peak_flops": 1e12,
            "hbm_bytes": 8e9},
}


def normalize_chip(device_kind: str) -> str:
    """Map a PJRT `device_kind` string ("TPU v5 lite", "cpu", ...) to
    a CHIP_SPECS key. A kind with no row is an error, never an assumed
    v5e: a roofline against the wrong peaks ranks the wrong config."""
    name = (device_kind or "").lower()
    if "cpu" in name or "host" in name:
        return "cpu"
    if "v5 lite" in name or "v5e" in name or "v5litepod" in name:
        return "v5e"
    if "v5p" in name or name.endswith("v5") or "v5 " in name:
        return "v5p"
    if "v6" in name:
        return "v6e"
    if "v4" in name:
        return "v4"
    raise ValueError(
        f"no CHIP_SPECS row for device kind {device_kind!r}; known "
        f"chips: {sorted(CHIP_SPECS)}")


# ---------------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------------
def default_config(space: Optional[Dict] = None) -> Dict:
    """All-defaults config: the first value of every knob."""
    sp = KNOBS if space is None else space
    return {k: vals[0] for k, vals in sp.items()}


def validate_config(cfg: Dict, space: Optional[Dict] = None) -> Dict:
    """Reject unknown knob NAMES and unknown knob VALUES loudly — a
    typo'd knob silently tuning nothing is exactly the failure mode a
    refusal here prevents. Returns a full config (missing knobs filled
    with their defaults)."""
    sp = KNOBS if space is None else space
    unknown = set(cfg) - set(sp)
    if unknown:
        raise ValueError(
            f"unknown knob name(s) {sorted(unknown)}; known: "
            f"{sorted(sp)}")
    out = default_config(sp)
    for k, v in cfg.items():
        if v not in sp[k]:
            raise ValueError(
                f"unknown value {v!r} for knob {k!r}; known: "
                f"{list(sp[k])}")
        out[k] = v
    return out


def canonical(cfg: Dict) -> str:
    """Stable JSON identity of a config (sorted keys) — the
    deterministic tie-break and the measured-score match key."""
    return json.dumps(cfg, sort_keys=True, default=str)


def _non_default_count(cfg: Dict, space: Optional[Dict] = None) -> int:
    sp = KNOBS if space is None else space
    return sum(1 for k, v in cfg.items()
               if k in sp and v != sp[k][0])


# ---------------------------------------------------------------------------
# Observability: cache_stats()["tuning"]
# ---------------------------------------------------------------------------
class _TuningStats:
    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.proposals = 0
        self.scored = 0
        self.score_cache_hits = 0
        self.measured_hits = 0
        self.infeasible = 0
        self.store_loads = 0
        self.store_saves = 0

    def snapshot(self) -> Dict:
        return {
            "proposals": self.proposals,
            "scored": self.scored,
            "score_cache_hits": self.score_cache_hits,
            "measured_hits": self.measured_hits,
            "infeasible": self.infeasible,
            "store_loads": self.store_loads,
            "store_saves": self.store_saves,
        }


_STATS = _TuningStats()
stats_mod.register_cache("tuning", _STATS)


def tuning_stats() -> _TuningStats:
    return _STATS


# ---------------------------------------------------------------------------
# Measured score sources (the TVM lesson: real numbers outrank the
# model wherever they exist)
# ---------------------------------------------------------------------------
class MeasuredScores:
    """Measured examples/sec per exact config, plus per-knob Pallas
    sweep timings. `lookup(cfg)` returns a measured score only on an
    EXACT canonical match — a near-miss silently standing in for a
    measurement would poison the frontier."""

    def __init__(self):
        self._by_config: Dict[str, float] = {}
        # pallas knob -> {value: best score seen}; normalized
        # (us/us_ref) and raw-microsecond records are kept in
        # SEPARATE pools — ranking a ratio against a raw time would
        # always prefer whichever value happened to carry the
        # reference measurement
        self._pallas_norm: Dict[str, Dict] = {}
        self._pallas_raw: Dict[str, Dict] = {}

    def add_config(self, cfg: Dict, examples_per_sec: float) -> None:
        self._by_config[canonical(cfg)] = float(examples_per_sec)

    def lookup(self, cfg: Dict) -> Optional[float]:
        return self._by_config.get(canonical(cfg))

    def add_pallas(self, knob: str, value, us: float,
                   us_ref: Optional[float] = None) -> None:
        """Record one sweep timing. When the XLA reference time is
        known the stored score is the NORMALIZED ratio us/us_ref —
        one knob can be swept by several cases (ROW_BUDGET rides both
        the xent and dropout sweeps) and by interpret-mode AND
        on-chip runs appended to the same JSONL; raw microseconds
        from different workloads/modes are incomparable, ratios to
        each case's own XLA baseline are scale-free."""
        pool = self._pallas_norm if us_ref else self._pallas_raw
        score = us / us_ref if us_ref else us
        d = pool.setdefault(knob, {})
        if value not in d or score < d[value]:
            d[value] = float(score)

    def best_pallas_value(self, knob: str):
        """argmin value for one pallas knob (None when unswept).
        Normalized records win outright when any exist for the knob —
        they are the workload-comparable pool."""
        d = self._pallas_norm.get(knob) or self._pallas_raw.get(knob)
        if not d:
            return None
        return min(sorted(d, key=lambda v: (v is None, v)),
                   key=lambda v: d[v])

    def pallas_knobs_swept(self) -> List[str]:
        return sorted(set(self._pallas_norm) | set(self._pallas_raw))


def ingest_pallas_jsonl(path: str,
                        into: Optional[MeasuredScores] = None
                        ) -> MeasuredScores:
    """Read the per-config JSONL emitted by
    `benchmarks/pallas_tune.py --jsonl`: records
    {"case", "knob", "value", "us", "us_ref"} keyed by the env-var
    knob name. Partial trailing lines (a killed sweep) are skipped —
    the `trace.read_metrics` contract."""
    ms = into if into is not None else MeasuredScores()
    env_to_knob = {v: k for k, v in PALLAS_ENV.items()}
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return ms
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            r = json.loads(line)
        except ValueError:
            continue  # partial trailing line
        knob = env_to_knob.get(r.get("knob"), r.get("knob"))
        if knob in PALLAS_ENV and "us" in r:
            ref = r.get("us_ref")
            ms.add_pallas(knob, r.get("value"), float(r["us"]),
                          us_ref=float(ref) if ref else None)
    return ms


def ingest_metrics_jsonl(path: str,
                         into: Optional[MeasuredScores] = None,
                         chip: Optional[str] = None,
                         batch: Optional[int] = None
                         ) -> MeasuredScores:
    """Read measured examples/sec from a metrics JSONL whose records
    carry a `config` dict (a run that logs its effective config
    beside its rate). Records without a config are
    skipped — there is nothing exact to match them to. `chip`/`batch`
    filters (pass the chip being tuned and the effective batch being
    scored) drop records measured elsewhere: a CPU toy-geometry run's
    tens of img/s must never override a v5e candidate's modeled
    thousands — the exact frontier-poisoning `MeasuredScores.lookup`'s
    exact-match rule exists to prevent. A filtered field missing from
    a record fails CLOSED (skipped)."""
    ms = into if into is not None else MeasuredScores()
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return ms
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            r = json.loads(line)
        except ValueError:
            continue
        cfg = r.get("config")
        eps = r.get("measured_examples_per_sec",
                    r.get("examples_per_sec"))
        if chip is not None and r.get("chip") != chip:
            continue
        if batch is not None and r.get("batch") != batch:
            continue
        if isinstance(cfg, dict) and eps and r.get(
                "source") == "measured":
            try:
                ms.add_config(validate_config(cfg), float(eps))
            except ValueError:
                continue  # foreign schema: not this knob space
    return ms


# ---------------------------------------------------------------------------
# The scorer
# ---------------------------------------------------------------------------
class CostModelScorer:
    """Scores one config WITHOUT a chip.

    `model_factory()` must return a fresh `(model, optimizer)` pair
    per call (configs mutate optimizer slot policy and process knobs,
    so instances are never reused across configs);
    `make_inputs()` returns the effective-batch input Tensors
    (inputs-then-labels, exactly what `train_one_batch` takes).

    Scoring lowers the whole-step program at the config's MICROBATCH
    geometry (grad_accum=n scans n microbatches whose per-iteration
    cost is what the roofline needs; the analytic step estimate is
    n x the microbatch lowering, which over-counts the once-per-step
    optimizer apply by (n-1) — a conservative bias against
    accumulation, documented here rather than hidden) and reads the
    traffic/FLOP meters there; `peak_bytes` comes from the FULL accum
    geometry's pre-optimization HLO (the real scan program, where a
    remat policy's smaller saveable set shrinks the loop body's max
    live set — pre-opt text pays tracing but no second XLA compile).
    Results are cached per HLO-affecting knob
    subset (HLO_KNOBS): xla/pallas axes are meter-neutral, so configs
    differing only there share one measurement.
    """

    def __init__(self, model_factory: Callable,
                 make_inputs: Callable,
                 chip: str = "v5e",
                 measured: Optional[MeasuredScores] = None):
        if chip not in CHIP_SPECS:
            raise ValueError(
                f"unknown chip {chip!r}; known: {sorted(CHIP_SPECS)}")
        self.model_factory = model_factory
        self.make_inputs = make_inputs
        self.chip = chip
        self.measured = measured
        self._hlo_cache: Dict[tuple, Dict] = {}
        self._fingerprint: Optional[str] = None

    @property
    def fingerprint(self) -> Optional[str]:
        """Topology fingerprint of the scored model (available after
        the first score): the store key."""
        return self._fingerprint

    def _hlo_key(self, cfg: Dict) -> tuple:
        def h(v):
            return tuple(v) if isinstance(v, (list, tuple)) else v

        return tuple((k, h(cfg[k])) for k in HLO_KNOBS)

    def _measure(self, cfg: Dict) -> Dict:
        """Lower the step under this config's HLO-affecting knobs and
        read the meters. Process knobs are snapshotted and restored —
        scoring must never leak a candidate's knobs into the live
        process."""
        from . import hlo_profile

        from . import tensor as tensor_mod

        n = int(cfg["grad_accum"])
        # Multi-axis knobs (ISSUE 10): a mesh geometry compiles the
        # step as the SPMD program over a ParallelPlan mesh and the
        # roofline divides by the device count (SPMD splits bytes and
        # flops; the collectives' traffic is the documented
        # approximation error). Infeasible geometries (axis product
        # not dividing the process's devices) score -inf with a loud
        # reason instead of erroring — the knob space is shared
        # between 1-device CI and the 8-device mesh.
        geom = cfg["mesh_geometry"]
        plan = None
        ndev = 1
        if geom is not None:
            from .parallel import plan as plan_mod

            axes = plan_mod.parse_geometry(geom)
            plan = plan_mod.ParallelPlan(**axes)
            try:
                # the real feasibility oracle: auto_mesh's own rules
                # (explicit axes must use the devices exactly; a
                # divisor-only pre-check would admit e.g. an 8-device
                # geometry on a 16-device backend and then crash the
                # sweep inside compile)
                mesh = plan.build_mesh()
            except ValueError as e:
                _STATS.infeasible += 1
                return {"feasible": False, "score": float("-inf"),
                        "reason": f"mesh {geom}: {e}"}
            ndev = 1
            for v in mesh.shape.values():
                ndev *= int(v)
        saved = stats_mod.get_config()
        saved_cd = tensor_mod.get_compute_dtype()
        try:
            tensor_mod.set_compute_dtype(cfg["compute_dtype"])
            stats_mod.configure(
                bn_stats_dtype=cfg["bn_stats_dtype"],
                remat_policy=cfg["remat_policy"],
                grad_accum=1,
                pipeline_microbatches=cfg["pipeline_microbatches"],
                moe_capacity_factor=cfg["moe_capacity_factor"],
                # donation off for the measurement: the aliasing
                # copies XLA inserts for donated buffers are noise on
                # top of the program's real dataflow (the
                # test_byte_diet metering discipline)
                buffer_donation=False)
            model, optimizer = self.model_factory()
            if cfg["slot_dtype"] is not None:
                optimizer.set_slot_dtype(cfg["slot_dtype"])
            model.set_optimizer(optimizer)
            inputs = self.make_inputs()
            batch = int(inputs[0].shape[0])
            if batch % n:
                _STATS.infeasible += 1
                return {"feasible": False, "score": float("-inf"),
                        "reason": f"batch {batch} not divisible by "
                                  f"grad_accum {n}"}
            plan_kw = {} if plan is None else {"plan": plan}
            mb_inputs = [self._slice_mb(t, batch // n) for t in inputs]
            model.compile([mb_inputs[0]], is_train=True,
                          use_graph=True, grad_accum=1, **plan_kw)
            if self._fingerprint is None:
                self._fingerprint = model.topology_fingerprint()
            opt_text = model.step_hlo_text(*mb_inputs)
            mb_bytes = hlo_profile.bytes_accessed(opt_text)["total"]
            mb_flops = sum(r["flops"]
                           for r in hlo_profile.profile_hlo(opt_text))
            if n > 1:
                # Peak liveness must be metered on the REAL program —
                # the n-microbatch scan, where the estimator recurses
                # into the loop body and a remat policy's smaller
                # saveable set actually shrinks the max live set
                # (tests/test_remat_policy.py pins the strict drop).
                # Pre-optimization text only: no second XLA compile.
                stats_mod.configure(grad_accum=n)
                full_model, full_opt = self.model_factory()
                if cfg["slot_dtype"] is not None:
                    full_opt.set_slot_dtype(cfg["slot_dtype"])
                full_model.set_optimizer(full_opt)
                full_model.compile([inputs[0]], is_train=True,
                                   use_graph=True, grad_accum=n,
                                   **plan_kw)
                pre_text = full_model.step_hlo_text(
                    *inputs, optimized=False)
            else:
                pre_text = model.step_hlo_text(*mb_inputs,
                                               optimized=False)
            peak = hlo_profile.peak_bytes_estimate(pre_text)
        finally:
            tensor_mod.set_compute_dtype(saved_cd)
            stats_mod.configure(
                bn_stats_dtype=saved["bn_stats_dtype"],
                remat_policy=saved["remat_policy"],
                grad_accum=saved["grad_accum"],
                pipeline_microbatches=saved["pipeline_microbatches"],
                moe_capacity_factor=saved["moe_capacity_factor"],
                buffer_donation=saved["buffer_donation"])
        spec = CHIP_SPECS[self.chip]
        step_bytes = n * mb_bytes
        step_flops = n * mb_flops
        # CHIP_SPECS peaks are the MXU's native bf16 numbers; fp32
        # compute runs at roughly half of it — the flops side of the
        # AMP knob (the bytes side is measured directly).
        peak_flops = spec["peak_flops"] * (
            1.0 if cfg["compute_dtype"] == "bfloat16" else 0.5)
        # Mesh geometries meter the GLOBAL SPMD program: per-chip
        # roofline time divides bytes/flops/liveness by the device
        # count (SPMD splits the work; collective traffic rides inside
        # the measured bytes — a conservative over-count per chip).
        est = max(step_bytes / ndev / (spec["hbm_gbps"] * 1e9),
                  step_flops / ndev / peak_flops)
        feasible = peak / ndev <= spec["hbm_bytes"]
        if not feasible:
            _STATS.infeasible += 1
        return {
            "feasible": feasible,
            "score": (batch / est if feasible and est > 0
                      else float("-inf")),
            "est_step_s": est,
            "bytes": step_bytes,
            "flops": step_flops,
            "mb_bytes": mb_bytes,
            "peak_bytes": peak,
            "n_devices": ndev,
            "effective_batch": batch,
            "microbatch": batch // n,
        }

    @staticmethod
    def _slice_mb(t, mb: int):
        from . import tensor as tensor_mod

        if int(t.shape[0]) == mb:
            return t
        return tensor_mod.from_raw(t.data[:mb], t.device)

    def score(self, cfg: Dict) -> Dict:
        """Full score row for one (validated) config: cost-model
        roofline, measured override when an exact match exists, cache
        hit accounting."""
        cfg = validate_config(cfg)
        key = self._hlo_key(cfg)
        cached = key in self._hlo_cache
        if cached:
            _STATS.score_cache_hits += 1
            base = dict(self._hlo_cache[key])
        else:
            base = self._measure(cfg)
            self._hlo_cache[key] = dict(base)
            _STATS.scored += 1
        base["cached"] = cached
        base["source"] = "cost-model"
        base["chip"] = self.chip
        base["config"] = dict(cfg)
        if self.measured is not None:
            m = self.measured.lookup(cfg)
            if m is not None and base.get("feasible", False):
                base["score"] = m
                base["source"] = "measured"
                _STATS.measured_hits += 1
        return base


# ---------------------------------------------------------------------------
# Deterministic search
# ---------------------------------------------------------------------------
def propose(space: Optional[Dict] = None, budget: int = 16,
            seed: int = 0,
            measured: Optional[MeasuredScores] = None) -> List[Dict]:
    """Deterministic candidate list, coordinate-descent flavored:

      1. the default config (the baseline every comparison needs),
      2. every SINGLE-knob flip in knob/value enumeration order —
         the axis sweep that isolates each knob's own effect (and
         costs almost nothing for HLO-neutral axes: the score cache
         collapses them onto the default's measurement),
      3. seeded random fill from the remaining cartesian product when
         budget remains.

    No wall clock, no global RNG — `seed` alone fixes the proposals.
    When `measured` carries Pallas sweep data, candidates' swept
    pallas knobs snap to their measured-best values (that axis was
    already searched for real; the budget goes to the axes only the
    cost model can rank). `autotune` reserves one extra slot for the
    greedy combination of the winning single flips."""
    sp = KNOBS if space is None else space
    if budget < 1:
        raise ValueError("budget must be >= 1")
    keys = list(sp)
    base = default_config(sp)
    picks = [dict(base)]
    for k in keys:
        for v in sp[k][1:]:
            picks.append(dict(base, **{k: v}))
    if len(picks) > budget:
        picks = picks[:budget]
    elif len(picks) < budget:
        # Random fill samples the cartesian product BY INDEX — the
        # full space runs to millions of configs for the real KNOBS
        # ladder, so materializing it (the old implementation) cost
        # ~65 s per call. `random.sample` draws positions, not
        # values, so sampling `range(n_rest)` and mixed-radix
        # decoding each index yields the exact candidate list the
        # materialized version produced for every (space, budget,
        # seed) — determinism contract unchanged.
        sizes = [len(sp[k]) for k in keys]
        strides = [0] * len(keys)
        acc = 1
        for i in range(len(keys) - 1, -1, -1):
            strides[i] = acc
            acc *= sizes[i]
        total = acc
        seen_ix = sorted({
            sum(sp[k].index(c[k]) * strides[i]
                for i, k in enumerate(keys)) for c in picks})
        rng = random.Random(seed)
        need = min(budget - len(picks), total - len(seen_ix))
        for j in rng.sample(range(total - len(seen_ix)), need):
            # shift past the already-picked (single-flip) indices to
            # land on the j-th REMAINING config in product order
            for s in seen_ix:
                if s <= j:
                    j += 1
                else:
                    break
            picks.append({k: sp[k][(j // strides[i]) % sizes[i]]
                          for i, k in enumerate(keys)})
    if measured is not None:
        snapped = []
        seen = set()
        for c in picks:
            c = dict(c)
            for knob in measured.pallas_knobs_swept():
                if knob in c and c[knob] == sp[knob][0]:
                    # only non-swept (default) positions snap: the
                    # axis-sweep candidates for the pallas knob itself
                    # must stay distinct
                    best = measured.best_pallas_value(knob)
                    if best in sp.get(knob, ()):
                        c[knob] = best
            key = canonical(c)
            if key not in seen:
                seen.add(key)
                snapped.append(c)
        picks = snapped
    _STATS.proposals += len(picks)
    return picks


def _greedy_combo(rows: List[Dict], space: Dict) -> Optional[Dict]:
    """Combine, per knob, the best single-flip value that STRICTLY
    beat the baseline row — the coordinate-descent exploitation step.
    None when no flip improved (or the combo isn't novel). The
    baseline is rows[0]'s CONFIG, not `default_config` — with a
    Pallas sweep armed, `propose` snaps every candidate's untouched
    pallas knobs to the measured best (baseline included), so flips
    must be measured against the snapped baseline or no row would
    ever differ by exactly one knob."""
    base = rows[0]["config"]
    base_score = rows[0]["score"]
    combo = dict(base)
    improved = False
    for k in space:
        best_v, best_s = base[k], base_score
        for r in rows:
            cfg = r["config"]
            diffs = [kk for kk in space
                     if cfg.get(kk, space[kk][0]) != base[kk]]
            if diffs == [k] and r.get("feasible") \
                    and r["score"] > best_s:
                best_v, best_s = cfg[k], r["score"]
        if best_v != base[k]:
            combo[k] = best_v
            improved = True
    if not improved:
        return None
    seen = {canonical(r["config"]) for r in rows}
    return combo if canonical(combo) not in seen else None


def autotune(scorer: CostModelScorer, budget: int = 16, seed: int = 0,
             space: Optional[Dict] = None,
             jsonl_path: Optional[str] = None,
             log: Optional[Callable] = None) -> Dict:
    """Run the search: propose -> score -> pick. Appends one JSON line
    per candidate to `jsonl_path` and returns
    {"best", "best_score", "default_score", "rows", ...}. Winner
    selection is a pure function of the scored rows: max score, then
    FEWEST non-default knobs (never flip a knob the model can't
    justify), then canonical JSON — so reruns with the same seed
    produce the same winner, always."""
    sp = KNOBS if space is None else space
    # one budget slot is reserved for the greedy combination of the
    # winning single flips (the exploitation step)
    proposals = propose(sp, budget=max(1, budget - 1), seed=seed,
                        measured=scorer.measured)
    rows = []
    sink = None
    if jsonl_path:
        d = os.path.dirname(jsonl_path)
        if d:
            os.makedirs(d, exist_ok=True)
        sink = open(jsonl_path, "a")

    def run_one(i, cfg, tag=""):
        row = scorer.score(cfg)
        row["i"] = i
        row["seed"] = seed
        rows.append(row)
        if sink is not None:
            clean = {k: v for k, v in row.items()
                     if v != float("-inf")}
            sink.write(json.dumps(clean, default=str) + "\n")
            sink.flush()
        if log is not None:
            log(f"[{i + 1}] score={row['score']:.1f} "
                f"{'(cached) ' if row['cached'] else ''}{tag}"
                f"{_fmt_cfg(row['config'], sp)}")
        return row

    try:
        for i, cfg in enumerate(proposals):
            run_one(i, cfg)
        if len(rows) < budget:
            combo = _greedy_combo(rows, sp)
            if combo is not None:
                run_one(len(rows), combo, tag="combo: ")
    finally:
        if sink is not None:
            sink.close()
    feasible = [r for r in rows if r.get("feasible")]
    pool = feasible if feasible else rows

    def rank(r):
        # max score; then fewest non-default knobs (never flip a knob
        # the model can't justify); then EARLIEST proposal — knob/
        # value enumeration order, so ties resolve to the first-listed
        # (preferred) value deterministically
        return (r["score"], -_non_default_count(r["config"], sp),
                -r["i"])

    best = max(pool, key=rank)
    default_row = rows[0]
    return {
        "best": best["config"],
        "best_score": best["score"],
        "best_row": best,
        "default_score": default_row["score"],
        "default_row": default_row,
        "beats_default": best["score"] > default_row["score"],
        "evaluated": len(rows),
        "rows": rows,
        "seed": seed,
        "chip": scorer.chip,
    }


def _fmt_cfg(cfg: Dict, space: Optional[Dict] = None) -> str:
    sp = KNOBS if space is None else space
    nd = {k: v for k, v in cfg.items()
          if k in sp and v != sp[k][0]}
    return "default" if not nd else " ".join(
        f"{k}={v}" for k, v in sorted(nd.items()))


# ---------------------------------------------------------------------------
# Persistent best-known store
# ---------------------------------------------------------------------------
STORE_SCHEMA = 1


def default_store_path() -> str:
    """`SINGA_TPU_TUNED_STORE` env override, else
    `.tuned/tuned_configs.json` under the working directory."""
    return os.environ.get("SINGA_TPU_TUNED_STORE") or os.path.join(
        ".tuned", "tuned_configs.json")


class TunedStore:
    """JSON store of best-known configs keyed by
    `(topology fingerprint, chip kind)`, plus a name->fingerprint
    alias map so a caller can resolve "resnet" before the
    model's params exist. Writes are atomic (tmp + os.replace); a
    corrupt store reads as empty with a loud stderr note — a bad
    cache entry must cost a re-tune, never a crash."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_store_path()

    def _read(self) -> Dict:
        try:
            with open(self.path) as f:
                data = json.load(f)
            if data.get("schema") != STORE_SCHEMA:
                raise ValueError(
                    f"schema {data.get('schema')} != {STORE_SCHEMA}")
            return data
        except FileNotFoundError:
            return {"schema": STORE_SCHEMA, "entries": {},
                    "aliases": {}}
        except (OSError, ValueError) as e:
            import sys

            print(f"singa_tpu: tuned store {self.path!r} unreadable "
                  f"({type(e).__name__}: {e}); treating as empty",
                  file=sys.stderr)
            return {"schema": STORE_SCHEMA, "entries": {},
                    "aliases": {}}

    def put(self, fingerprint: str, chip: str, config: Dict,
            score: float, provenance: Optional[Dict] = None,
            alias=None) -> Dict:
        """`alias` may be one name or a list of them — a model is
        commonly addressed at several granularities ("resnet-18" AND
        "resnet"); all map to this fingerprint, latest put wins."""
        config = validate_config(config)
        data = self._read()
        entry = {
            "config": config,
            "score": float(score),
            "chip": chip,
            "fingerprint": fingerprint,
            "provenance": dict(provenance or {},
                               created=time.time()),
        }
        data["entries"][f"{fingerprint}@{chip}"] = entry
        for a in ([alias] if isinstance(alias, str) else alias or ()):
            data["aliases"][a] = fingerprint
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)
        _STATS.store_saves += 1
        return entry

    def get(self, fingerprint: Optional[str] = None,
            alias: Optional[str] = None,
            chip: Optional[str] = None) -> Optional[Dict]:
        data = self._read()
        fp = fingerprint
        if fp is None and alias is not None:
            fp = data["aliases"].get(alias)
        if fp is None:
            return None
        if chip is not None:
            ent = data["entries"].get(f"{fp}@{chip}")
            if ent is not None:
                _STATS.store_loads += 1
            return ent
        for key in sorted(data["entries"]):
            if key.startswith(f"{fp}@"):
                _STATS.store_loads += 1
                return data["entries"][key]
        return None

    def entries(self) -> Dict:
        return self._read()["entries"]


# ---------------------------------------------------------------------------
# Applying a config to the live process
# ---------------------------------------------------------------------------
def apply_config(cfg: Dict, optimizer=None, apply_xla: bool = False,
                 training: bool = True) -> Dict:
    """Arm the process knobs a config names. `optimizer` receives the
    slot-dtype policy when given. `apply_xla=True` also applies the
    XLA flag profile — only meaningful BEFORE backend init (bench
    stage subprocesses; see device.set_xla_profile). Pallas block
    knobs export their env vars (read at ops/pallas_kernels import —
    arm them before the first singa_tpu.ops import to take effect).
    `training=False` applies only the forward-safe subset (BN stats
    floor + pallas envs): the serving tier must not arm training
    geometry. Returns the applied subset."""
    from . import device

    cfg = validate_config(cfg)
    applied: Dict = {}
    if apply_xla and cfg["xla_profile"] != "default":
        device.set_xla_profile(cfg["xla_profile"])
        applied["xla_profile"] = cfg["xla_profile"]
    if cfg["bn_stats_dtype"] is not None:
        device.set_bn_stats_dtype(cfg["bn_stats_dtype"])
        applied["bn_stats_dtype"] = cfg["bn_stats_dtype"]
    # inference-only knob: forward-safe by construction (training
    # steps never read it), so it applies in BOTH modes
    if cfg["inference_quant"] != "off":
        device.set_inference_quant(cfg["inference_quant"])
        applied["inference_quant"] = cfg["inference_quant"]
    import sys as _sys

    pk = _sys.modules.get("singa_tpu.ops.pallas_kernels")
    for knob, env in PALLAS_ENV.items():
        if cfg[knob] is not None:
            os.environ[env] = str(cfg[knob])
            if pk is not None:
                setattr(pk, PALLAS_ATTR[knob], int(cfg[knob]))
            applied[knob] = cfg[knob]
    if training:
        if cfg["compute_dtype"] is not None:
            from . import tensor as tensor_mod

            tensor_mod.set_compute_dtype(cfg["compute_dtype"])
            applied["compute_dtype"] = cfg["compute_dtype"]
        if cfg["grad_accum"] != 1:
            device.set_grad_accum(cfg["grad_accum"])
            applied["grad_accum"] = cfg["grad_accum"]
        if cfg["remat_policy"] is not None:
            device.set_remat_policy(cfg["remat_policy"])
            applied["remat_policy"] = cfg["remat_policy"]
        if optimizer is not None and cfg["slot_dtype"] is not None:
            optimizer.set_slot_dtype(cfg["slot_dtype"])
            applied["slot_dtype"] = cfg["slot_dtype"]
        # multi-axis trainer knobs (ISSUE 10): training geometry —
        # never armed for serving
        if cfg["mesh_geometry"] is not None:
            from .parallel import plan as plan_mod

            device.set_parallel_plan(
                plan_mod.plan_from_geometry(cfg["mesh_geometry"]))
            applied["mesh_geometry"] = cfg["mesh_geometry"]
        if cfg["pipeline_microbatches"] is not None:
            from . import stats as _stats

            _stats.configure(
                pipeline_microbatches=cfg["pipeline_microbatches"])
            applied["pipeline_microbatches"] = \
                cfg["pipeline_microbatches"]
        if cfg["moe_capacity_factor"] is not None:
            from . import stats as _stats

            _stats.configure(
                moe_capacity_factor=cfg["moe_capacity_factor"])
            applied["moe_capacity_factor"] = cfg["moe_capacity_factor"]
    return applied


def _current_chip() -> str:
    import jax

    d = jax.devices()[0]
    return normalize_chip(f"{d.platform} {d.device_kind}")


def load_best(model=None, alias: Optional[str] = None,
              chip: Optional[str] = None,
              store_path: Optional[str] = None) -> Optional[Dict]:
    """Best-known entry for a model (by live topology fingerprint) or
    an alias, on `chip` (default: the current backend's kind), with
    an any-chip fallback: the autotuner models the TARGET chip (v5e)
    even on a CPU backend, so a strict live-chip lookup would find
    nothing in every CI/off-chip environment. None when the store has
    nothing — callers fall back to defaults. The returned entry names
    its `chip`; consumers log it."""
    store = TunedStore(store_path)
    if not os.path.exists(store.path):
        return None
    fp = model.topology_fingerprint() if model is not None else None
    return store.get(fingerprint=fp, alias=alias,
                     chip=chip or _current_chip()) \
        or store.get(fingerprint=fp, alias=alias)


def apply_best_for_serving(model, store_path: Optional[str] = None
                           ) -> Optional[Dict]:
    """The serving tier's default-load hook (`serve.ServingEngine`):
    look the model up in the tuned store and arm the FORWARD-SAFE
    subset of its best-known config (BN-stats floor, pallas block
    envs — never training geometry). A missing store or entry is a
    silent no-op; a hit is one stderr line so operators can see which
    config is serving."""
    try:
        ent = load_best(model=model, store_path=store_path)
    except Exception:
        return None
    if ent is None:
        return None
    try:
        applied = apply_config(ent["config"], training=False)
    except ValueError:
        return None
    if applied:
        import sys

        print("singa_tpu: serving with tuned config "
              f"{applied} (score {ent.get('score'):.1f}, chip "
              f"{ent.get('chip')})", file=sys.stderr)
    return ent
