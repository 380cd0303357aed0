"""AOT executable cache: persistent `jax.export` artifacts + the
shape-bucketing policy (ISSUE 6; ROADMAP item 4).

Why: a fleet of training/serving workers cannot pay Python tracing +
XLA compilation per process or per novel batch shape — r05 burned
~73 s per probe on recompiles before the compile-cache env export, and
the persistent XLA cache only removes the *compile* half. This module
removes the *trace* half: the whole-step (`model._JitStep`), mesh-step
(`parallel.trainer.ShardedJitStep`), and forward-only
(`model._JitForward`) executables are serialized with `jax.export`
into an on-disk store, and a fresh process deserializes the StableHLO
artifact instead of re-tracing the user's Python — milliseconds where
tracing took seconds. PHAST (arXiv:2005.13076) and the GPU-to-CPU
transpilation work (arXiv:2207.00257) both argue for portable compiled
artifacts as the interchange point between build time and run time;
`jax.export`'s versioned StableHLO is exactly that artifact here.

Keying: an artifact may load ONLY when it would trace identically.
The key hashes (a) the model topology fingerprint
(`Model.topology_fingerprint`: class + source + param/state inventory;
`sonnx.SONNXModel` overrides with the ONNX graph digest, so imported
graphs warm-start too), (b) the abstract argument signature
(shapes/dtypes/tree structure — post-bucketing, so the bucket IS the
key), (c) a snapshot of every step-affecting knob — remat policy, slot
dtype, BN-stats dtype, grad-accum geometry, step guard, loss scaling,
XLA profile, AMP compute dtype, matmul precision, optimizer
hyperparameters — (d) the platform: jax version, backend, device
kind, device count, plus mesh extras for sharded steps — and (e) a
digest of the `singa_tpu` package's own source files
(`package_digest`): the op lowerings and the optimizer update are
framework code, so an edit there is a miss too. A knob change changes
the key; a stale artifact can never load.

Integrity: every artifact gets a digest manifest sidecar (sha256 +
size, the `checkpoint.CheckpointManager` idiom). A corrupt/truncated
artifact is reported loudly and the caller falls back to tracing —
a bad cache entry costs one trace, never a wrong program.
`tools/export_cache_gc.py` lists / validates / garbage-collects the
store.

Bucketing: `BucketPolicy` rounds batch (and optionally sequence) dims
up to the next power of two, bounded by an explicit maximum — a shape
above the largest bucket is a LOUD error, not a silent retrace.
`pad_batch_to_bucket` pads at dispatch by repeating the final sample
(`data.microbatches`' pad idiom); the forward path slices padded rows
back off, so under diverse traffic the number of distinct traced
shapes — and therefore retraces and artifacts — is bounded by the
bucket count. Counters: `cache_stats()["export"]` (hits / misses /
saves / errors / traces / load_s / trace_s / bucket_pads /
buckets_seen / step_retraces).

Knobs: `device.set_export_cache(dir)` arms the store;
`device.set_shape_buckets(max_batch=..., seq_dim=..., max_seq=...)`
arms the bucketing policy (each works without the other).
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

from . import stats as stats_mod

__all__ = [
    "BucketPolicy",
    "BucketOverflowError",
    "configure",
    "active",
    "bucket_policy",
    "pad_batch_to_bucket",
    "pad_batch",
    "batch_mask",
    "step_key",
    "artifact_exists",
    "load",
    "export_and_save",
    "note_step_retrace",
    "list_artifacts",
    "validate_artifact",
]

# Artifact schema version: bump to orphan every prior artifact (key
# component, not a runtime check).
SCHEMA = 1

_CONFIG: Dict = {
    # Artifact store directory (None = cache off).
    "directory": None,
    # BucketPolicy or None (bucketing works independently of the store:
    # without a directory it still bounds live retraces).
    "buckets": None,
}


class BucketOverflowError(ValueError):
    """A dispatched shape exceeds the largest configured bucket.

    Deliberately loud: silently tracing an unbounded shape is exactly
    the retrace storm the policy exists to prevent — the caller must
    either raise the bucket ceiling or reject the request."""


class BucketPolicy:
    """Powers-of-two shape buckets with explicit ceilings.

    `max_batch` bounds the batch (dim 0) bucket ladder; `seq_dim` /
    `max_seq` optionally bucket a sequence dimension too (right-pad
    semantics — safe for causal attention, where later positions never
    influence earlier ones; bidirectional models should bucket batch
    only). Ceilings must be powers of two so the ladder has no
    unreachable gap between the top bucket and the ceiling.
    """

    def __init__(self, max_batch: int = 4096,
                 seq_dim: Optional[int] = None,
                 max_seq: Optional[int] = None):
        self.max_batch = int(max_batch)
        self.seq_dim = None if seq_dim is None else int(seq_dim)
        self.max_seq = None if max_seq is None else int(max_seq)
        for name, v in (("max_batch", self.max_batch),
                        ("max_seq", self.max_seq)):
            if v is not None and (v < 1 or v & (v - 1)):
                raise ValueError(
                    f"BucketPolicy {name} must be a power of two >= 1, "
                    f"got {v}")
        if self.seq_dim is not None and self.max_seq is None:
            raise ValueError("seq_dim set but max_seq missing")
        if self.max_seq is not None and self.seq_dim is None:
            # the converse is equally a silent misconfiguration: a
            # ceiling with no dimension to bucket is dead code the
            # caller believes is armed
            raise ValueError("max_seq set but seq_dim missing")

    @staticmethod
    def _bucket(n: int, ceiling: int, what: str) -> int:
        if n < 1:
            raise ValueError(f"cannot bucket empty {what} dim ({n})")
        if n > ceiling:
            raise BucketOverflowError(
                f"{what} size {n} exceeds the largest configured "
                f"bucket ({ceiling}); raise the ceiling "
                "(device.set_shape_buckets) or reject the request — "
                "silently tracing an unbounded shape defeats the "
                "bucketing policy")
        b = 1
        while b < n:
            b <<= 1
        return b

    def bucket_batch(self, n: int) -> int:
        return self._bucket(int(n), self.max_batch, "batch")

    def bucket_seq(self, n: int) -> int:
        return self._bucket(int(n), self.max_seq, "sequence")

    def n_buckets(self) -> int:
        """Upper bound on distinct bucketed shapes per dimension set:
        len({1, 2, 4, ..., max_batch}) x len(seq ladder)."""
        out = self.max_batch.bit_length()
        if self.max_seq is not None:
            out *= self.max_seq.bit_length()
        return out

    def describe(self) -> Dict:
        return {"max_batch": self.max_batch, "seq_dim": self.seq_dim,
                "max_seq": self.max_seq}


def configure(**kw) -> Dict:
    """Update export-cache knobs (`directory`, `buckets`). User-facing
    setters live on `singa_tpu.device` (`set_export_cache`,
    `set_shape_buckets`)."""
    for k, v in kw.items():
        if k not in _CONFIG:
            raise KeyError(
                f"unknown export_cache config key {k!r}; known: "
                f"{sorted(_CONFIG)}")
        if k == "directory" and v is not None:
            v = str(v) or None  # "" means off (the env-var contract)
            if v is not None:
                os.makedirs(v, exist_ok=True)
        if k == "buckets" and v is not None and not isinstance(
                v, BucketPolicy):
            raise ValueError("buckets must be a BucketPolicy or None")
        _CONFIG[k] = v
    return dict(_CONFIG)


def active() -> bool:
    return _CONFIG["directory"] is not None


def directory() -> Optional[str]:
    """The armed store directory (None when the cache is off) — what
    a fleet parent hands its worker subprocesses so every replica
    deserializes from the SAME store (populate-once-start-N)."""
    return _CONFIG["directory"]


def bucket_policy() -> Optional[BucketPolicy]:
    return _CONFIG["buckets"]


# ---------------------------------------------------------------------------
# Observability: cache_stats()["export"]
# ---------------------------------------------------------------------------
class _ExportStats:
    """Counters for the AOT artifact store + bucketing policy.

    `traces` counts step/forward executables actually TRACED in this
    process (the cost warm starts avoid — a fully warm process shows
    traces=0); `load_s`/`trace_s` are the cumulative wall seconds the
    two paths cost, which is how a time to the first step splits
    into trace/compile/load. `step_retraces` counts post-warmup
    abstract-shape changes on the step path (the retrace-storm
    warning's counter). `buckets_seen` is the number of distinct
    bucketed dispatch shapes — under the policy it is bounded by
    `BucketPolicy.n_buckets()`, which is what turns the retrace
    counter into a provisioning signal."""

    def __init__(self):
        self.reset()
        self._buckets = set()

    def reset(self) -> None:
        self.hits = 0
        self.misses = 0
        self.saves = 0
        self.errors = 0
        self.traces = 0
        self.step_retraces = 0
        self.bucket_pads = 0
        self.load_s = 0.0
        self.trace_s = 0.0
        # buckets_seen describes live dispatch diversity, reset with
        # the counters (a fresh measurement window starts clean)
        self._buckets = set()

    def note_bucket(self, sig) -> None:
        self._buckets.add(sig)

    def snapshot(self) -> Dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "saves": self.saves,
            "errors": self.errors,
            "traces": self.traces,
            "step_retraces": self.step_retraces,
            "bucket_pads": self.bucket_pads,
            "buckets_seen": len(self._buckets),
            "load_s": round(self.load_s, 6),
            "trace_s": round(self.trace_s, 6),
            "dir": _CONFIG["directory"] or "",
        }


_STATS = _ExportStats()
stats_mod.register_cache("export", _STATS)


def export_stats() -> _ExportStats:
    return _STATS


# ---------------------------------------------------------------------------
# Key computation
# ---------------------------------------------------------------------------
def _scalarize(v, depth: int = 2):
    """JSON-able, ADDRESS-FREE projection of a config value: scalars
    pass through, containers recurse, callables key on their code (two
    different schedules/statics must not collide), arrays on
    shape/dtype/content digest, and other objects flatten to class
    name + scalar attrs (one level) — `repr` would embed `0x...`
    addresses and make keys process-unique, which would defeat the
    cache."""
    if isinstance(v, (int, float, str, bool, type(None))):
        return v
    if isinstance(v, (list, tuple)):
        return [_scalarize(x, depth) for x in v]
    if isinstance(v, (set, frozenset)):
        return sorted(str(x) for x in v)
    if isinstance(v, dict):
        return {str(k): _scalarize(x, depth) for k, x in sorted(v.items())}
    code = getattr(v, "__code__", None)
    if code is not None:
        # plain function/lambda: identity = name + bytecode + embedded
        # constants (two lambdas differing only in a literal must not
        # collide)
        return {"__callable__": f"{getattr(v, '__module__', '')}."
                                f"{getattr(v, '__qualname__', '')}",
                "code": hashlib.sha256(code.co_code).hexdigest(),
                "consts": [_scalarize(c, 0) for c in code.co_consts]}
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        import numpy as np

        arr = np.asarray(v)
        return {"__array__": [list(map(int, arr.shape)),
                              str(arr.dtype)],
                "sha256": (hashlib.sha256(arr.tobytes()).hexdigest()
                           if arr.size <= (1 << 20) else None)}
    if depth <= 0:
        return type(v).__name__
    # objects — callable instances (LR schedules) included: their
    # hyperparameters live in __dict__ and MUST key (an artifact bakes
    # the schedule's constants into the traced program), and their
    # behavior lives in __call__'s code
    out = {"__class__": type(v).__name__}
    call_code = getattr(getattr(type(v), "__call__", None), "__code__",
                        None)
    if callable(v) and call_code is not None:
        out["__call_code__"] = hashlib.sha256(
            call_code.co_code).hexdigest()
    for k, a in sorted(getattr(v, "__dict__", {}).items()):
        out[k] = _scalarize(a, depth - 1)
    return out


def _opt_fingerprint(opt):
    """Optimizer identity for the key: class + every scalar
    hyperparameter (lr, momentum, weight decay, slot dtype, schedule
    params...). Runtime state is excluded — `states` and
    `step_counter` are program INPUTS, not program structure."""
    if opt is None:
        return None
    out = {"class": type(opt).__name__}
    targets = [("", opt)]
    inner = getattr(opt, "opt", None)
    if inner is not None and inner is not opt:
        targets.append(("inner.", inner))
    for prefix, o in targets:
        for k, v in sorted(getattr(o, "__dict__", {}).items()):
            if k in ("states", "step_counter", "opt") or k.startswith(
                    "_fused") or k.startswith("_accum"):
                continue
            out[prefix + k] = _scalarize(v)
    return out


def knob_fingerprint() -> Dict:
    """Snapshot of every process knob that changes the traced step:
    the contract that makes a stale artifact unloadable."""
    from . import autograd, device, tensor

    from .ops import pallas_kernels

    cfg = stats_mod.get_config()
    remat = getattr(autograd, "_remat", False)
    return {
        # train/eval mode: dropout and BatchNorm trace DIFFERENT
        # programs (eval BN normalizes by running stats and never
        # updates them) — a train-mode forward artifact silently
        # reused for inference would be a correctness bug, so the mode
        # rides the knob snapshot for every executable kind, not just
        # the forward extras.
        "train_mode": bool(autograd.training),
        # pallas tier: flash-attention vs plain attention are
        # DIFFERENT traced programs behind the same model code
        "pallas": pallas_kernels.enabled(),
        # stats-owned step-affecting knobs (dag cache capacity/policy
        # and the eager auto-route threshold do NOT change the traced
        # graph-mode program and are deliberately excluded)
        "bn_stats_dtype": cfg.get("bn_stats_dtype"),
        "step_guard": cfg.get("step_guard"),
        "loss_scaling": _scalarize(cfg.get("loss_scaling")),
        "grad_accum": cfg.get("grad_accum"),
        "remat": _scalarize(remat),
        # scan-level remat policy (ISSUE 9): a policy flip re-derives
        # the backward (checkpointed-region vjp vs captured walk) —
        # a different traced program, so it must orphan artifacts
        "remat_policy": _scalarize(cfg.get("remat_policy")),
        "compute_dtype": str(tensor.get_compute_dtype()),
        "matmul_precision": tensor.get_matmul_precision(),
        "xla_profile": device.get_xla_profile(),
        # Multi-axis trainer knobs (ISSUE 10): the process-default
        # ParallelPlan selects mesh/schedule at compile time, and the
        # pipeline-microbatch / MoE-capacity overrides change the
        # traced schedule geometry — all three must orphan artifacts
        # on flip (a per-model compile(plan=...) rides the sharded
        # step's extras instead).
        "parallel_plan": _scalarize(_process_plan_fp()),
        "pipeline_microbatches": cfg.get("pipeline_microbatches"),
        "moe_capacity_factor": cfg.get("moe_capacity_factor"),
        # int8 quantized inference (ISSUE 19): int8 params + packed
        # KV slab trace a DIFFERENT decode/forward program — flipping
        # the knob must orphan fp32 artifacts (and vice versa), never
        # load them stale.
        "inference_quant": cfg.get("inference_quant", "off"),
    }


def _process_plan_fp():
    from .parallel import plan as plan_mod

    p = plan_mod.process_plan()
    return None if p is None else p.fingerprint()


def _args_signature(args) -> Dict:
    """Abstract signature of a program-argument pytree: per-leaf
    shape/dtype plus the tree structure (two different arg nestings
    with identical leaves must not collide)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return ["arr", [int(d) for d in x.shape], str(x.dtype)]
        if isinstance(x, (bool, int, float, complex)):
            # Python scalars enter the jit as TRACED weak-typed values
            # — the program depends on their type, never their value.
            # Keying on the value (e.g. the optimizer step counter)
            # would make every resumed run a guaranteed miss and grow
            # the store one artifact per starting step.
            return ["pyscalar", type(x).__name__]
        return ["py", repr(x)]

    return {"tree": str(treedef), "leaves": [leaf(x) for x in leaves]}


_PACKAGE_DIR = os.path.dirname(os.path.abspath(__file__))


@functools.lru_cache(maxsize=None)
def package_digest(root: str = _PACKAGE_DIR) -> str:
    """sha256 over every `.py` file under `root` (relative path +
    bytes, sorted walk) — the framework half of the export key. Op
    lowerings, the optimizer update and the step wrappers live in the
    package, not in the user's model class, so an edit to any of them
    must be a store miss. Memoized per process: the files a process
    imported do not change under it."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()


def step_key(model, opt, kind: str, args,
             extras=None) -> Tuple[str, Dict]:
    """(sha256 hex key, human-readable parts) for one executable.

    `kind` distinguishes the step vs forward program family; `extras`
    carries per-subclass identity (the mesh layout for sharded steps,
    training flag + statics for forwards)."""
    import jax

    d = jax.devices()[0]
    dev_kind = f"{d.platform}/{d.device_kind}"
    parts = {
        "schema": SCHEMA,
        # the framework's own source rides the key (not a version
        # string nobody bumps): an artifact exported by an older
        # autograd/opt/ops can never load under the edited code
        "singa_tpu": package_digest(),
        "kind": kind,
        "model": model.topology_fingerprint(),
        "model_class": type(model).__qualname__,
        "opt": _opt_fingerprint(opt),
        "knobs": knob_fingerprint(),
        "args": _args_signature(args),
        "jax": jax.__version__,
        "device_kind": dev_kind,
        "n_devices": jax.device_count(),
        "extras": _scalarize(extras),
    }
    blob = json.dumps(parts, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest(), parts


# ---------------------------------------------------------------------------
# Artifact store
# ---------------------------------------------------------------------------
ARTIFACT_SUFFIX = ".jexp"
MANIFEST_SUFFIX = ".jexp.json"


def _paths(key: str) -> Tuple[str, str]:
    base = os.path.join(_CONFIG["directory"], key[:32])
    return base + ARTIFACT_SUFFIX, base + MANIFEST_SUFFIX


def artifact_exists(key: str) -> bool:
    """Whether the store holds an artifact for `key` (existence only —
    `load` still digest-checks). The prewarm tool's `--dry-run` probe:
    answers "would this executable warm-start?" without deserializing,
    tracing, or touching the hit/miss counters."""
    return active() and os.path.exists(_paths(key)[0])


def load(key: str):
    """Deserialize the artifact for `key`, or None (miss / corrupt).

    The digest manifest is verified BEFORE deserialization (the
    `CheckpointManager` contract): a truncated or bit-rotted artifact
    is reported loudly, counted in `errors`, and the caller falls back
    to tracing — never a crash, never a silently wrong program."""
    path, man_path = _paths(key)
    if not os.path.exists(path):
        _STATS.misses += 1
        return None
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            blob = f.read()
        if os.path.exists(man_path):
            with open(man_path) as f:
                man = json.load(f)
            if len(blob) != man.get("size"):
                raise IOError(
                    f"size mismatch (manifest {man.get('size')}, on "
                    f"disk {len(blob)} — truncated write?)")
            if hashlib.sha256(blob).hexdigest() != man.get("sha256"):
                raise IOError("content digest mismatch (corrupt "
                              "artifact)")
        from jax import export as jexport

        exp = jexport.deserialize(blob)
    except Exception as e:
        _STATS.errors += 1
        _STATS.misses += 1
        print(f"singa_tpu: export cache artifact {path!r} failed to "
              f"load ({type(e).__name__}: {e}); falling back to "
              "tracing", file=sys.stderr)
        return None
    _STATS.hits += 1
    _STATS.load_s += time.perf_counter() - t0
    return exp


def export_and_save(key: str, parts: Dict, jitted, args):
    """Trace+lower `jitted` with `jax.export`, persist the artifact
    (atomic publish + digest manifest sidecar), and return the
    `Exported`. Returns None when the program cannot be exported
    (host callbacks etc.) — reported loudly; the caller keeps the
    plain jit. A save failure never fails the step."""
    from jax import export as jexport

    t0 = time.perf_counter()
    try:
        exp = jexport.export(jitted)(*args)
    except Exception as e:
        # the trace WAS paid before export rejected the program —
        # count it, or a callback-bearing model reports traces=0
        # while tracing every process (indistinguishable from warm)
        _STATS.traces += 1
        _STATS.trace_s += time.perf_counter() - t0
        _STATS.errors += 1
        print(f"singa_tpu: jax.export failed for {parts.get('kind')} "
              f"({type(e).__name__}: {e}); this executable will not "
              "warm-start", file=sys.stderr)
        return None
    _STATS.traces += 1
    _STATS.trace_s += time.perf_counter() - t0
    path, man_path = _paths(key)
    # per-process tmp names: fleet workers missing on the same key
    # concurrently must not interleave writes into one tmp file (the
    # os.replace publish itself is atomic either way)
    tmp_tag = f".tmp.{os.getpid()}"
    try:
        blob = exp.serialize()
        tmp = path + tmp_tag
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)  # atomic publish
        man = {
            "sha256": hashlib.sha256(blob).hexdigest(),
            "size": len(blob),
            "created": time.time(),
            "key": key,
            # trimmed human-readable identity for the GC tool
            "meta": {
                "kind": parts.get("kind"),
                "model_class": parts.get("model_class"),
                "device_kind": parts.get("device_kind"),
                "n_devices": parts.get("n_devices"),
                "jax": parts.get("jax"),
                "knobs": parts.get("knobs"),
            },
        }
        mtmp = man_path + tmp_tag
        with open(mtmp, "w") as f:
            json.dump(man, f)
        os.replace(mtmp, man_path)
        _STATS.saves += 1
    except Exception as e:
        _STATS.errors += 1
        for victim in (path + tmp_tag, man_path + tmp_tag):
            try:
                os.remove(victim)
            except OSError:
                pass
        print(f"singa_tpu: export cache save failed for {path!r} "
              f"({type(e).__name__}: {e}); continuing untraced",
              file=sys.stderr)
    return exp


def count_trace(seconds: float) -> None:
    """A step/forward executable was traced WITHOUT the store (cache
    off): keeps `traces`/`trace_s` meaning 'tracing paid by this
    process' in both modes."""
    _STATS.traces += 1
    _STATS.trace_s += seconds


# ---------------------------------------------------------------------------
# Retrace-storm diagnosis (satellite)
# ---------------------------------------------------------------------------
def _fmt_sig(sig) -> str:
    return ", ".join(f"{dt}[{','.join(str(d) for d in shape)}]"
                     for shape, dt in sig)


def note_step_retrace(old_sig, new_sig) -> None:
    """A compiled train step saw a NEW abstract batch signature after
    warmup — i.e. XLA is about to retrace. One line, naming old vs
    new, so the bare `retraces` counter finally says WHICH shapes are
    churning (and the fix: bucket them)."""
    _STATS.step_retraces += 1
    print("singa_tpu: step retrace after warmup — abstract batch "
          f"shapes changed from ({_fmt_sig(old_sig)}) to "
          f"({_fmt_sig(new_sig)}); feed fixed/bucketed batch sizes on "
          "the training side (data.microbatches pads tails), or "
          "device.set_shape_buckets for serving forwards",
          file=sys.stderr)


# ---------------------------------------------------------------------------
# Pad-to-bucket dispatch helpers
# ---------------------------------------------------------------------------
def _batch_leader(arrays) -> Optional[int]:
    """Batch size of a dispatch: dim 0 of the FIRST array that has
    one (the framework-wide shape-inference convention; 0-d leaves —
    a scalar timestep, say — ride along unbucketed)."""
    for a in arrays:
        if getattr(a, "ndim", 0) >= 1:
            return int(a.shape[0])
    return None


def pad_batch(arrays, n_target: int):
    """Right-pad dim 0 of every array sharing the leading batch dim up
    to `n_target` by REPEATING the final sample — `data.microbatches`'
    pad idiom (real values, no NaN/denormal hazards). Arrays whose
    dim 0 differs from the batch leader ride through untouched."""
    import jax.numpy as jnp
    import numpy as np

    n = _batch_leader(arrays)
    if n is None or n == n_target:
        return list(arrays)
    out = []
    for a in arrays:
        if getattr(a, "ndim", 0) < 1 or int(a.shape[0]) != n:
            out.append(a)
            continue
        tail = a[-1:]
        reps = [n_target - n] + [1] * (a.ndim - 1)
        if isinstance(a, np.ndarray):
            out.append(np.concatenate([a, np.tile(tail, reps)]))
        else:
            out.append(jnp.concatenate([a, jnp.tile(tail, reps)]))
    return out


def batch_mask(n_real: int, n_target: int, dtype="float32"):
    """[n_target] mask: 1 for real rows, exact 0 for pad rows. With
    sum-based masked reductions the pad rows contribute exact zeros,
    so masked loss/metrics match the unpadded step bit-for-bit on
    exact arithmetic (tests/test_export_cache.py proves it)."""
    import numpy as np

    m = np.zeros((n_target,), dtype=dtype)
    m[:n_real] = 1
    return m


def pad_batch_to_bucket(arrays, policy: Optional[BucketPolicy] = None):
    """Bucket-pad a dispatch batch: returns (padded_arrays, info)
    where info = {n_real, n_bucket, seq_real, seq_bucket, seq_dim}
    (the slicing recipe for the reply). Raises `BucketOverflowError`
    (loudly) above the top bucket. Also buckets `policy.seq_dim` when
    configured (right-pad by repeating the final position —
    causal-safe only; see BucketPolicy); `seq_real/seq_bucket` report
    the FIRST seq-bearing input, which is what reply slicing keys on."""
    import jax.numpy as jnp
    import numpy as np

    pol = policy if policy is not None else bucket_policy()
    n = _batch_leader(arrays)
    info = {"n_real": n, "n_bucket": n,
            "seq_real": None, "seq_bucket": None,
            "seq_dim": None if pol is None else pol.seq_dim}
    if pol is None or n is None:
        return list(arrays), info
    target = pol.bucket_batch(n)
    info["n_bucket"] = target
    out = pad_batch(arrays, target)
    padded = target != n
    if pol.seq_dim is not None:
        d = pol.seq_dim
        seq_out = []
        for a in out:
            if getattr(a, "ndim", 0) > d:
                s = int(a.shape[d])
                st = pol.bucket_seq(s)
                if info["seq_real"] is None:
                    info["seq_real"], info["seq_bucket"] = s, st
                if st != s:
                    tail = jnp.take(a, jnp.asarray([s - 1]), axis=d) \
                        if not isinstance(a, np.ndarray) \
                        else np.take(a, [s - 1], axis=d)
                    reps = [1] * a.ndim
                    reps[d] = st - s
                    tile = (np.tile if isinstance(a, np.ndarray)
                            else jnp.tile)(tail, reps)
                    cat = (np.concatenate if isinstance(a, np.ndarray)
                           else jnp.concatenate)
                    a = cat([a, tile], axis=d)
                    padded = True
            seq_out.append(a)
        out = seq_out
    if padded:
        _STATS.bucket_pads += 1
    _STATS.note_bucket(tuple(
        (tuple(int(d) for d in getattr(a, "shape", ())),
         str(getattr(a, "dtype", ""))) for a in out))
    return out, info


def slice_bucket_out(out_tree, info):
    """Undo bucket padding on a reply pytree: leaves carrying the
    bucketed batch dim are cut back to `n_real`, and (when seq
    bucketing applied) leaves carrying the bucketed seq dim are cut
    back to `seq_real`. Batch-ness/seq-ness is inferred by SHAPE —
    the `_merge_accum_out` caveat: avoid bucket ceilings equal to
    unrelated output dims."""
    import jax

    n_real, n_bucket = info["n_real"], info["n_bucket"]
    s_real, s_bucket = info["seq_real"], info["seq_bucket"]
    d = info["seq_dim"]

    def leaf(a):
        if (n_bucket != n_real and getattr(a, "ndim", 0) >= 1
                and a.shape[0] == n_bucket):
            a = a[:n_real]
        if (s_bucket is not None and s_bucket != s_real
                and getattr(a, "ndim", 0) > d and a.shape[d] == s_bucket):
            idx = [slice(None)] * a.ndim
            idx[d] = slice(0, s_real)
            a = a[tuple(idx)]
        return a

    return jax.tree_util.tree_map(leaf, out_tree)


# ---------------------------------------------------------------------------
# Store inventory (tools/export_cache_gc.py)
# ---------------------------------------------------------------------------
def validate_artifact(path: str, deep: bool = True) -> Optional[str]:
    """None when `path` passes its manifest check (or is a
    manifest-less legacy artifact, validated by deserialization at
    load); otherwise the reason it is invalid. `deep=False` stops at
    the stat-only size check — listing a fleet store must not re-read
    and hash gigabytes of artifacts just to print names."""
    man_path = path + ".json"
    try:
        size = os.path.getsize(path)
    except OSError as e:
        return f"unreadable artifact: {e}"
    if not os.path.exists(man_path):
        return None
    try:
        with open(man_path) as f:
            man = json.load(f)
    except (OSError, ValueError) as e:
        return f"unreadable manifest: {e}"
    if size != man.get("size"):
        return (f"size mismatch (manifest {man.get('size')}, on disk "
                f"{size} — truncated write?)")
    if not deep:
        return None
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    if h.hexdigest() != man.get("sha256"):
        return "content digest mismatch (corrupt artifact)"
    return None


def list_artifacts(directory: Optional[str] = None,
                   deep: bool = True) -> List[Dict]:
    """Inventory rows for every artifact in the store: path, size,
    created, manifest meta, and the validation verdict (stat-only
    when `deep=False`; see `validate_artifact`)."""
    d = directory or _CONFIG["directory"]
    if d is None or not os.path.isdir(d):
        return []
    rows = []
    for name in sorted(os.listdir(d)):
        if not name.endswith(ARTIFACT_SUFFIX):
            continue
        path = os.path.join(d, name)
        man_path = path + ".json"
        meta, created = {}, None
        if os.path.exists(man_path):
            try:
                with open(man_path) as f:
                    man = json.load(f)
                meta = man.get("meta", {})
                created = man.get("created")
            except (OSError, ValueError):
                pass
        rows.append({
            "path": path,
            "name": name,
            "size": os.path.getsize(path),
            "created": created,
            "meta": meta,
            "invalid": validate_artifact(path, deep=deep),
        })
    return rows
