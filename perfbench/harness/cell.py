"""Resolve a cell's name to its files, and hold what one run gathered.

BENCHMARK.json names cells, configurations and metrics; each resolves
to a file by that name, and a name that resolves to nothing is an
error that says which file is missing.
"""
import contextlib
import importlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load_json(path, what):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def benchmark(root=ROOT):
    return _load_json(os.path.join(root, "BENCHMARK.json"), "benchmark")


def load_cell(name, root=ROOT):
    """(cell entry, configuration, workload) for a cell of
    BENCHMARK.json. The configuration is its entry's `file`; the
    workload is perfbench/workloads/<cell>.json."""
    bench = benchmark(root)
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise KeyError(f"cell {name!r} names configuration "
                       f"{cell['config']!r}, which BENCHMARK.json lacks")
    config = _load_json(os.path.join(root, configs[cell["config"]]["file"]),
                        f"configuration {cell['config']!r}")
    workload = _load_json(
        os.path.join(root, "perfbench", "workloads", name + ".json"),
        f"traffic of cell {name!r}")
    return cell, config, workload


def metrics_for(cell_name, group, root=ROOT):
    """The entries of BENCHMARK.json's `end_to_end` or `per_layer` that
    this cell reports (an entry without `workloads` is for every cell)."""
    return [m for m in benchmark(root)[group]
            if cell_name in m.get("workloads", [cell_name])]


def module(package, name):
    """perfbench.<package>.<name>, or an error naming the file."""
    try:
        return importlib.import_module(f"perfbench.{package}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"perfbench.{package}.{name}":
            raise
        raise ModuleNotFoundError(
            f"no perfbench/{package}/{name}.py") from None


def resolve_callable(spec, root=ROOT):
    """"package.module:callable", or "path/to/file.py:callable" for a
    file that is no package (its directory joins sys.path, as the
    file's own sibling imports need)."""
    target, _, attr = spec.partition(":")
    if target.endswith(".py"):
        path = os.path.join(root, target)
        if not os.path.isfile(path):
            raise FileNotFoundError(f"builder {spec!r}: no file {path}")
        d = os.path.dirname(path)
        if d not in sys.path:
            sys.path.insert(0, d)
        mod = importlib.import_module(
            os.path.splitext(os.path.basename(path))[0])
    else:
        mod = importlib.import_module(target)
    return getattr(mod, attr)


def build(spec, root=ROOT):
    """{"callable": ..., "args": [...], "kwargs": {...}} -> the object."""
    return resolve_callable(spec["callable"], root)(
        *spec.get("args", []), **spec.get("kwargs", {}))


def set_policies(section):
    """The process-wide numeric policies a driver section states:
    matmul precision, AMP compute dtype, the Pallas tier."""
    from singa_tpu import tensor
    from singa_tpu.ops import pallas_kernels

    tensor.set_matmul_precision(section["matmul_precision"])
    tensor.set_compute_dtype(section["compute_dtype"])
    pallas_kernels.enable(bool(section.get("pallas", False)))


@dataclass
class Run:
    """What a driver hands back: the end-to-end numbers it took itself,
    and what the per-layer readers read."""
    cell: dict
    config: dict
    workload: dict
    seconds: float
    trace: bool
    seed: int = 0
    t_process_start: float = 0.0
    meter: object = None
    peaks: dict = None
    end_to_end: dict = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    wrong: list = field(default_factory=list)   # why `correct` is false
    spans: list = field(default_factory=list)   # program's trace.records()
    counters: dict = field(default_factory=dict)  # program counter deltas
    samples: dict = field(default_factory=dict)   # the benchmark's own clocks
    compiles_in_window: int = 0
    device_trace: object = None                 # xplane.Trace
    trace_window_ns: tuple = None
    notes: dict = field(default_factory=dict)   # for the human-readable lines
    marks: list = field(default_factory=list)   # (phase, perf_counter)

    def mark(self, phase, at=None):
        """The set-up phase that ends now, or at `at` (for the line that
        says what `setup_s` is made of)."""
        self.marks.append((phase, time.perf_counter() if at is None else at))

    def setup_phases(self):
        out, t = [], self.t_process_start
        for phase, at in self.marks:
            out.append(f"{phase} {at - t:.1f}")
            t = at
        return " + ".join(out) + " s"

    def annotate(self, name):
        """The benchmark's own span around a call into the program,
        written into the profiler's trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.TraceAnnotation("bench:" + name)
