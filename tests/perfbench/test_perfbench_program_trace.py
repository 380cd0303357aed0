"""The program's spans on the device trace's clock
(`harness/program_trace.py`) and the nine per-layer metrics that read
them: on hand-made tuples, on three dispatcher cycles cut out of each
serving cell's trace on the chip (`fixtures/*.cycles.json`;
`record_program_trace.py` says how), and through a traced toy run on
the CPU whose profiler session is real and whose device is made up."""
import glob
import json
import os
import shutil
import time

import jax
import pytest

from perfbench import run as run_mod
from perfbench.harness import cell as cell_mod
from perfbench.harness import compile_meter, peaks, profiler
from perfbench.harness import program_trace as pt
from perfbench.harness import xplane
from perfbench.harness.xplane import Trace

HERE = os.path.dirname(__file__)
TOY = os.path.join(HERE, "toy")
FIXTURES = os.path.join(HERE, "fixtures")
SERVING = ["gpt2-serve-decode", "gpt2-serve-short"]
TRACE_CLOCK = ["idle_readback_pct", "idle_dispatch_pct", "idle_hostwork_pct",
               "idle_nowork_pct", "idle_unattributed_pct",
               "readback_tail_ms_p50"]
RING = ["decode_dispatch_ms_p50", "token_scatter_ms_p50",
        "decode_queue_wait_ms_p90"]
IDLE = TRACE_CLOCK[:5]       # the five shares of the device's idle time


def _reader(name):
    return cell_mod.module("layer_metrics", name)


# -- arithmetic on hand-made tuples -------------------------------------------
def _cycle(t):
    """One dispatcher cycle of 100 ns from `t`: the leaves in the
    loop's order, no two overlapping, 2 ns between admit and the
    prefill in no span at all."""
    names = ["decode.admit", "decode.prefill.assemble",
             "decode.prefill.dispatch", "decode.prefill.readback",
             "decode.prefill.scatter", "decode.step.assemble",
             "decode.step.dispatch", "decode.step.readback",
             "decode.step.scatter"]
    edges = [0, 8, 14, 24, 40, 46, 50, 60, 90, 100]
    spans = [(n, t + a, t + b) for n, a, b in zip(names, edges, edges[1:])]
    spans[0] = ("decode.admit", t, t + 6)
    return spans


def _device(t):
    """The chip in that cycle: the prefill runs 20-34, the step 56-78."""
    return [("fusion.prefill", t + 20, t + 34),
            ("fusion.step", t + 56, t + 78)]


def test_flatten_keeps_the_innermost_span():
    spans = [("outer", 0, 100), ("inner", 10, 30), ("innermost", 15, 20),
             ("next", 120, 130), ("empty", 40, 40)]
    assert pt.flatten(spans) == [
        ("outer", 0, 10), ("inner", 10, 15), ("innermost", 15, 20),
        ("inner", 20, 30), ("outer", 30, 100), ("next", 120, 130)]
    # spans of two threads may overlap without nesting: the later start
    # has it until it ends, and no instant is counted twice
    assert pt.flatten([("a", 0, 10), ("b", 5, 15)]) == [
        ("a", 0, 5), ("b", 5, 15)]
    assert pt.flatten([]) == []


def test_five_buckets_add_up_to_the_windows_idle():
    spans = _cycle(0) + _cycle(100) + [("decode.wait_work", 200, 250)]
    devices = {0: _device(0) + _device(100)}
    w0, w1 = 0, 260
    by_span = pt.idle_by_span(devices, spans, w0, w1)
    idle = 260 - 2 * (14 + 22)
    assert sum(by_span.values()) == pytest.approx(idle)
    b = pt.buckets(by_span)
    assert set(b) == {"readback", "dispatch", "hostwork", "nowork",
                      "unattributed"}
    assert sum(b.values()) == pytest.approx(idle)
    # per cycle: dispatch idle 14-20 and 50-56; readback 34-40 and 78-90;
    # the 2 ns after admit and the 10 ns after the last wait are in no span
    assert b["dispatch"] == 2 * 12 and b["readback"] == 2 * 18
    assert b["nowork"] == 50 and b["unattributed"] == 2 * 2 + 10
    assert b["hostwork"] == idle - 24 - 36 - 50 - 14
    tr = Trace(devices=devices)
    red = pt.reduce(devices, spans, w0, w1)
    assert sum(red["idle_pct"].values()) == pytest.approx(
        xplane.idle_pct(tr, w0, w1))
    assert red["cycles"] == 2 and red["window_ns"] == 260
    line = pt.describe(red)
    assert "over 2 cycles" in line and "unattributed" in line
    assert all(name.removeprefix("decode.") in line for name in pt.LEAVES)


def test_a_gap_crossing_four_spans_is_split_among_them():
    """The chip stops at 78; the next operation starts at 120: one gap
    of 42 ns that runs through readback, scatter, the next admit and
    the next prefill's assemble and dispatch."""
    spans = _cycle(0) + _cycle(100)
    devices = {0: [("fusion.step", 56, 78), ("fusion.prefill", 120, 134)]}
    by_span = pt.idle_by_span(devices, spans, 78, 120)
    assert {k: v for k, v in by_span.items() if v} == {
        "decode.step.readback": 12, "decode.step.scatter": 10,
        "decode.admit": 6, "unattributed": 2,
        "decode.prefill.assemble": 6, "decode.prefill.dispatch": 6}
    # two chips: the mean of the two; a chip that never works is idle
    # under every span for all of it
    two = pt.idle_by_span({0: devices[0], 1: []}, spans, 78, 120)
    assert two["decode.step.readback"] == 12
    two = pt.idle_by_span({0: devices[0], 1: [("f", 78, 90)]}, spans, 78, 120)
    assert two["decode.step.readback"] == 6


def test_innermost_span_wins_under_nesting():
    """A leaf opened inside another takes its own stretch; a span that
    is no leaf of the dispatcher is not looked at."""
    spans = [("decode.step.dispatch", 0, 100),
             ("decode.step.readback", 40, 60),
             ("shard_place", 10, 90), ("step", 0, 200)]
    by_span = pt.idle_by_span({0: [("f", 0, 10)]}, spans, 0, 200)
    assert by_span["decode.step.readback"] == 20
    assert by_span["decode.step.dispatch"] == 70
    assert by_span["unattributed"] == 100


@pytest.mark.parametrize("ops,tail", [
    ([("f", 10, 40)], 30),                  # done before the span opened
    ([("f", 10, 72)], 18),                  # done inside it
    ([("a", 10, 55), ("b", 55, 66), ("while", 50, 70), ("c", 66, 70),
      ("next", 95, 99)], 20),               # several: the last one's end
    ([("late", 95, 99)], 30),               # none before it closed
    ([], 30),
], ids=["before", "inside", "several", "only-later", "none"])
def test_readback_tail(ops, tail):
    spans = [("decode.step.dispatch", 50, 60),
             ("decode.step.readback", 60, 90),
             ("decode.prefill.readback", 20, 30)]
    assert pt.readback_tails({0: ops}, spans, 0, 100) == [tail]
    # a span the window cuts is not counted
    assert pt.readback_tails({0: ops}, spans, 65, 100) == []
    red = pt.reduce({0: ops}, spans, 0, 100)
    assert red["readback_tail_ms_p50"] == pytest.approx(tail / 1e6)


def test_an_idle_gap_takes_the_name_of_the_phase_over_its_midpoint():
    """`breakdown.idle_gaps` in a serving run: the dispatcher's leaf
    that covers the gap's midpoint, before the benchmark's own
    annotation; the driver's word where no leaf does."""
    spans = _cycle(0) + _cycle(100)
    tr = Trace(devices={0: _device(0) + _device(100)},
               host=[("bench:window", 0, 200), ("bench:submit_decode", 0, 30)])
    gaps = xplane.idle_gaps(tr, 0, 200, 5, "engine-thread", pt.leaves(spans))
    # longest first: 78-120 (42 ns, midpoint 99 in the step's scatter),
    # 34-56 and 134-156 (22: midpoints 45 in the prefill's scatter),
    # 178-200 (the step's readback), 0-20 (the prefill's assemble,
    # though `submit_decode` covers that midpoint too)
    assert gaps == [["decode.step.scatter", pytest.approx(42e-9)],
                    ["decode.prefill.scatter", pytest.approx(22e-9)],
                    ["decode.prefill.scatter", pytest.approx(22e-9)],
                    ["decode.step.readback", pytest.approx(22e-9)],
                    ["decode.prefill.assemble", pytest.approx(20e-9)]]
    # a midpoint in none of the leaves (6-8 of a cycle is in no span)
    tr = Trace(devices={0: [("f", 0, 6), ("f", 8, 100)]},
               host=[("bench:window", 0, 100)])
    assert xplane.idle_gaps(tr, 0, 100, 5, "engine-thread",
                            pt.leaves(spans)) == [
        ["engine-thread", pytest.approx(2e-9)]]
    assert pt.reduce(tr.devices, spans, 0, 200)["leaves"] == pt.leaves(spans)


# -- readers with nothing to read ---------------------------------------------
def _run(name="toy-serve-open", **kw):
    cell, config, workload = cell_mod.load_cell(name, TOY)
    return cell_mod.Run(cell=cell, config=config, workload=workload,
                        seconds=1.0, trace=True, **kw)


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """Keep the profiler's directory out of the checkout."""
    monkeypatch.setattr(
        profiler, "trace_dir",
        lambda name, root=None: str(tmp_path / "trace" / name))
    return lambda name: profiler.trace_dir(name)


def _a_trace():
    return Trace(devices={0: [("fusion.1", 0, 600)]},
                 host=[("bench:window", 0, 1000)])


@pytest.mark.parametrize("name", TRACE_CLOCK + RING)
@pytest.mark.parametrize("case", ["untraced", "no-file", "no-spans"])
def test_reader_returns_none_with_nothing_to_read(name, case, trace_dir):
    """An untraced run; a device trace whose file is gone; and the
    parent's case: a trace file and a span ring that hold none of the
    dispatcher's spans."""
    run = _run()
    if case != "untraced":
        run.device_trace, run.trace_window_ns = _a_trace(), (0, 1000)
        run.spans = [{"name": "decode_step", "ts": 0.0, "dur": 5.0,
                      "args": {"steps": 1}}]
    if case == "no-spans":
        d = os.path.join(trace_dir(run.cell["name"]), "plugins", "profile",
                         "x")
        os.makedirs(d)
        shutil.copy(os.path.join(FIXTURES, "v5e_gpt2_train.xplane.pb"),
                    os.path.join(d, "host.xplane.pb"))
    assert _reader(name).read(run) is None
    assert "idle_split" not in run.notes


def test_ring_readers_read_their_span():
    run = _run(spans=[
        {"name": "decode.step.dispatch", "ts": 0.0, "dur": 2000.0,
         "args": {"steps": 1}},
        {"name": "decode.step.dispatch", "ts": 9.0, "dur": 4000.0,
         "args": {"steps": 8}},
        {"name": "decode.step.scatter", "ts": 5.0, "dur": 500.0},
        {"name": "decode.prefill.dispatch", "ts": 5.0, "dur": 9e6},
        {"name": "queue_wait", "ts": 5.0, "dur": 9e6},
        *({"name": "decode_queue_wait", "ts": 1.0, "dur": 1e3 * k,
           "trace": "t"} for k in range(11))])
    assert _reader("decode_dispatch_ms_p50").read(run) == pytest.approx(3.0)
    assert _reader("token_scatter_ms_p50").read(run) == pytest.approx(0.5)
    assert _reader("decode_queue_wait_ms_p90").read(run) == pytest.approx(9.0)


# -- three cycles recorded on the chip ----------------------------------------
@pytest.mark.parametrize("cell", SERVING)
def test_recorded_cycles_reduce_to_what_the_run_printed(cell):
    with open(os.path.join(FIXTURES, f"v5e_{cell}.cycles.json")) as f:
        rec = json.load(f)
    assert rec["cell"] == cell
    spans = [tuple(s) for s in rec["spans"]]
    devices = {int(k): [tuple(o) for o in v]
               for k, v in rec["devices"].items()}
    red = pt.reduce(devices, spans, *rec["window_ns"])
    want = rec["printed"]
    assert red["cycles"] == want["cycles"] == 3
    assert red["idle_pct"] == pytest.approx(want["idle_pct"])
    assert red["idle_ns_by_span"] == pytest.approx(want["idle_ns_by_span"])
    assert red["readback_tail_ms_p50"] == pytest.approx(
        want["readback_tail_ms_p50"])
    # the five shares are the device's idle share, and the program's
    # leaves leave next to nothing of it unexplained
    idle = xplane.idle_pct(Trace(devices=devices), *rec["window_ns"])
    assert sum(red["idle_pct"].values()) == pytest.approx(idle)
    assert 0 <= red["idle_pct"]["unattributed"] < 2.0
    # on the chip the leaves of one thread do not overlap
    leaves = sorted((s for s in spans if s[0] in pt.LEAVES),
                    key=lambda s: s[1])
    assert all(a[2] <= b[1] for a, b in zip(leaves, leaves[1:]))


# -- a traced toy run: real profiler session, made-up device ------------------
class CpuDeviceTrace:
    """`profiler.DeviceTrace` on the CPU: the profiler session and the
    benchmark's window are real, so the program's "singa:" spans are
    in the file; the device's operations are made up from them: a chip
    that works from the middle of every dispatch span to the middle of
    the readback span after it."""

    def __init__(self, run):
        self.run = run
        self.dir = profiler.trace_dir(run.cell["name"])

    def __enter__(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self._win = jax.profiler.TraceAnnotation("bench:window")
        self._win.__enter__()
        return self

    def __exit__(self, *exc):
        self._win.__exit__(*exc)
        jax.profiler.stop_trace()
        path = xplane.newest_xplane(self.dir)
        tr = xplane.load(path)
        _, spans = pt.load(path)
        ops, start = [], None
        for name, t0, t1 in spans:
            if name.endswith(".dispatch"):
                start = (t0 + t1) // 2
            elif name.endswith(".readback") and start is not None:
                ops.append(("fusion.1", start, (t0 + t1) // 2))
                start = None
        tr.devices = {0: ops}
        self.run.device_trace = tr
        self.run.trace_window_ns = xplane.window(tr)
        return False


@pytest.fixture
def policies():
    """Drivers set process-wide numeric policies; put them back."""
    from singa_tpu import device, tensor, trace
    from singa_tpu.ops import pallas_kernels

    saved = (tensor.get_matmul_precision(), tensor.get_compute_dtype(),
             pallas_kernels.enabled(), trace.enabled())
    yield
    tensor.set_matmul_precision(saved[0])
    tensor.set_compute_dtype(saved[1])
    pallas_kernels.enable(saved[2])
    device.set_tracing(saved[3])
    trace.clear()


def the_nine(bench):
    """The nine metrics' entries of a BENCHMARK.json: there, and
    reported by the two GPT-2 serving cells, whatever cells join."""
    nine = [m for m in bench["per_layer"] if m["name"] in TRACE_CLOCK + RING]
    assert len(nine) == 9
    assert all(set(SERVING) <= set(m["workloads"]) for m in nine)
    return nine


@pytest.fixture
def toy_root(root, tmp_path):
    """The toy root with the nine's entries for its serving cells: the
    toy's own BENCHMARK.json is not this file's to edit."""
    toy = tmp_path / "toy"
    shutil.copytree(TOY, toy)
    with open(toy / "BENCHMARK.json") as f:
        bench = json.load(f)
    names = {m["name"] for m in bench["per_layer"]}
    for m in the_nine(cell_mod.benchmark(root)):
        assert m["name"] not in names
        bench["per_layer"].append(
            {**m, "workloads": ["toy-serve-closed", "toy-serve-open"]})
    with open(toy / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(toy)


_METER = []


@pytest.mark.parametrize("root", ["ours"], indirect=True)  # it runs a model
@pytest.mark.parametrize("name", ["toy-serve-closed", "toy-serve-open"])
def test_traced_toy_run_reports_the_nine_metrics(name, toy_root, trace_dir,
                                                 monkeypatch, policies):
    monkeypatch.setattr(profiler, "DeviceTrace", CpuDeviceTrace)
    if not _METER:        # listeners cannot be removed: one per process
        _METER.append(compile_meter.CompileMeter())
    cell, config, workload = cell_mod.load_cell(name, toy_root)
    # long enough that the open loop's 10 requests a second reach it;
    # the closed loop's sub-window is busy, and the profiler records
    # every Python call in it
    workload["trace_seconds"] = 0.3 if workload["loop"] == "closed" else 1.0
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=0.5, trace=True, seed=2**31 + 5,
                       t_process_start=time.perf_counter(), meter=_METER[0],
                       peaks=peaks.for_kind("TPU v5 lite"))
    driver = cell_mod.module("drivers", workload["driver"])
    driver.run(run)
    line = run_mod.result_line(run, jax.devices()[:1],
                               driver.UNATTRIBUTED_GAP, toy_root)
    assert line["correct"] is True, run.wrong
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(TRACE_CLOCK + RING) <= set(m), sorted(m)
    assert sum(m[k] for k in IDLE) == pytest.approx(
        m["serve_device_idle_pct"], abs=1e-6)
    assert all(m[k] >= 0 for k in TRACE_CLOCK + RING)
    # the made-up chip stops in the middle of every readback span
    assert m["idle_readback_pct"] > 0 and m["readback_tail_ms_p50"] > 0
    assert m["decode_dispatch_ms_p50"] > 0 and m["token_scatter_ms_p50"] > 0
    # the older readers still find their records
    assert m["decode_step_ms_p50"] > 0 and m["prefill_ms_p50"] > 0
    assert m["step_gap_ms_p50"] > 0
    assert run.notes["idle_split"].startswith("device idle ms per cycle")
    # the longest gaps carry the dispatcher's phase for a name
    labels = [label for label, _ in line["breakdown"]["idle_gaps"]]
    assert set(labels) <= set(pt.LEAVES) | {driver.UNATTRIBUTED_GAP}
    assert set(labels) & set(pt.LEAVES)
    assert len(glob.glob(os.path.join(
        trace_dir(name), "plugins", "profile", "*", "*.xplane.pb"))) == 1
