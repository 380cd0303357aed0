"""Observability layer (ISSUE 5): span tracer, Chrome trace export,
metrics JSONL, device-trace sink.

Contracts under test:
  - span nesting/ordering (thread-local stack; children close first),
  - the Chrome trace export is spec-conformant trace-event JSON and a
    traced train step decomposes into data_wait + dispatch +
    device_sync child spans,
  - MetricsLogger appends exactly ONE schema-stable record per train
    step (eager, graph, grad_accum=n, and the 8-device mesh path) and
    a SIGKILLed run leaves a parseable log,
  - disabled mode is a strict no-op (zero spans recorded),
  - `cache_stats()["trace"]` counters reset via `reset_cache_stats()`
    while the recorded timeline survives.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from singa_tpu import (
    autograd,
    data as data_mod,
    device,
    layer,
    metric,
    model,
    opt,
    resilience,
    stats,
    tensor,
    trace,
)
from singa_tpu.checkpoint import CheckpointManager
from singa_tpu.parallel import create_mesh

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _clean_trace():
    """Tracing/accum knobs are process-global: reset around every
    test."""
    stats.reset_cache_stats()
    trace.clear()
    yield
    device.set_tracing(False)
    trace.configure(ring_capacity=16384)
    trace.clear()
    stats.configure(grad_accum=1)
    stats.reset_cache_stats()


class MSEMLP(model.Model):
    def __init__(self):
        super().__init__()
        self.fc1 = layer.Linear(16)
        self.relu = layer.ReLU()
        self.fc2 = layer.Linear(4)

    def forward(self, x):
        return self.fc2(self.relu(self.fc1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = autograd.mse_loss(out, y)
        self._optimizer.backward_and_update(loss)
        return out, loss


_RS = np.random.RandomState(0)
_X = _RS.randn(32, 8).astype(np.float32)
_Y = _RS.randn(32, 4).astype(np.float32)


def _build(use_graph=True, grad_accum=None, mesh=None):
    m = MSEMLP()
    m.set_optimizer(opt.SGD(lr=0.05, momentum=0.5))
    tx, ty = tensor.from_numpy(_X), tensor.from_numpy(_Y)
    m.compile([tx], is_train=True, use_graph=use_graph, mesh=mesh,
              grad_accum=grad_accum)
    return m, tx, ty


# ---------------------------------------------------------------------------
# Span mechanics
# ---------------------------------------------------------------------------
def test_span_nesting_and_ordering():
    device.set_tracing(True)
    with trace.span("a"):
        with trace.span("b"):
            with trace.span("c"):
                pass
        with trace.span("d"):
            pass
    recs = trace.records()
    by = {r["name"]: r for r in recs}
    assert set(by) == {"a", "b", "c", "d"}
    assert by["a"]["depth"] == 0 and by["a"]["parent"] is None
    assert by["b"]["parent"] == by["a"]["id"] and by["b"]["depth"] == 1
    assert by["c"]["parent"] == by["b"]["id"] and by["c"]["depth"] == 2
    assert by["d"]["parent"] == by["a"]["id"] and by["d"]["depth"] == 1
    # records land at span EXIT: children close before parents
    names = [r["name"] for r in recs]
    assert names.index("c") < names.index("b") < names.index("a")
    # time containment
    for child, parent in (("b", "a"), ("c", "b"), ("d", "a")):
        assert by[child]["ts"] >= by[parent]["ts"]
        assert (by[child]["ts"] + by[child]["dur"]
                <= by[parent]["ts"] + by[parent]["dur"] + 1e-3)


def test_disabled_mode_records_zero_spans():
    assert not trace.enabled()
    # strict no-op: the SAME shared null context, no per-call object
    assert trace.span("x") is trace.span("y")
    with trace.span("x"):
        with trace.span("y"):
            pass
    with trace.step_span(0):
        pass
    assert trace.records() == []
    snap = stats.cache_stats()["trace"]
    assert snap["spans"] == 0 and snap["steps"] == 0
    assert trace.last_step_timings() is None


def test_ring_buffer_is_bounded_and_counts_drops():
    device.set_tracing(True, ring_capacity=8)
    for i in range(20):
        with trace.span(f"s{i}"):
            pass
    recs = trace.records()
    assert [r["name"] for r in recs] == [f"s{i}" for i in range(12, 20)]
    snap = stats.cache_stats()["trace"]
    assert snap["spans"] == 20 and snap["dropped"] == 12
    assert snap["ring_size"] == 8 and snap["ring_capacity"] == 8


def test_spans_are_thread_safe_and_nest_per_thread():
    device.set_tracing(True, ring_capacity=10000)

    def work():
        for _ in range(100):
            with trace.span("outer"):
                with trace.span("inner"):
                    pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert stats.cache_stats()["trace"]["spans"] == 800
    for r in trace.records():
        assert r["depth"] == (1 if r["name"] == "inner" else 0)


def test_trace_counters_reset_keeps_timeline():
    device.set_tracing(True)
    with trace.span("a"):
        pass
    assert stats.cache_stats()["trace"]["spans"] == 1
    stats.reset_cache_stats()
    snap = stats.cache_stats()["trace"]
    assert snap["spans"] == 0 and snap["dropped"] == 0
    assert snap["steps"] == 0 and snap["exports"] == 0
    # the recorded timeline survives the counter reset (same contract
    # as executable caches keeping their entries)
    assert len(trace.records()) == 1


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------
def test_chrome_export_is_spec_conformant(tmp_path):
    device.set_tracing(True)
    with trace.span("parent", tag="x"):
        with trace.span("child"):
            pass
    path = trace.export_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        data = json.load(f)
    evs = data["traceEvents"]
    assert isinstance(evs, list) and len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X"  # complete events
        for k in ("name", "ts", "dur", "pid", "tid"):
            assert k in ev, f"missing {k}"
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
    p = next(e for e in evs if e["name"] == "parent")
    c = next(e for e in evs if e["name"] == "child")
    assert p["ts"] <= c["ts"]
    assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-3
    assert p["args"]["tag"] == "x"
    assert stats.cache_stats()["trace"]["exports"] == 1


def test_step_decomposes_into_data_wait_dispatch_device_sync(tmp_path):
    """The acceptance shape: a graph-mode train step's chrome span
    nests data_wait, the compiled step's phases and the loop's own
    device_sync (its loss read, as `fit_resumable` spans it) — and the
    traced step itself never fences the device."""
    import jax

    device.set_tracing(True)
    m, tx, ty = _build(use_graph=True)
    fences = []
    real = jax.block_until_ready
    jax.block_until_ready = lambda x: (fences.append(1), real(x))[1]
    try:
        for k in range(3):
            with trace.step_span(k):
                with trace.span("data_wait"):
                    pass  # batch already device-resident
                _, loss = m(tx, ty)
                assert not fences, "a traced graph step fenced"
                with trace.span("device_sync"):
                    float(loss.to_numpy())
    finally:
        jax.block_until_ready = real
    path = trace.export_chrome_trace(str(tmp_path / "steps.json"))
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    steps = [e for e in evs if e["name"] == "step"]
    assert len(steps) == 3
    assert steps[-1]["args"]["step"] == 2
    last = steps[-1]
    kids = {e["name"] for e in evs
            if e is not last and last["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= last["ts"] + last["dur"] + 1e-3}
    assert {"data_wait", "step.call", "step.place", "step.enqueue",
            "step.bind", "device_sync"} <= kids, kids
    call = [e for e in evs if e["name"] == "step.call"][-1]
    for name in ("step.place", "step.enqueue", "step.bind"):
        e = [e for e in evs if e["name"] == name][-1]
        assert call["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= call["ts"] + call["dur"] + 1e-3, name
    t = trace.last_step_timings()
    assert t["step"] == 2 and t["step_s"] > 0
    assert t["dispatch_s"] > 0 and t["device_sync_s"] > 0
    # the summary table renders every wired span
    s = trace.format_summary()
    for name in ("step", "step.enqueue", "device_sync", "data_wait"):
        assert name in s


def test_eager_step_emits_train_and_apply_spans():
    device.set_tracing(True)
    m, tx, ty = _build(use_graph=False)
    m(tx, ty)
    names = {r["name"] for r in trace.records()}
    assert "train_one_batch" in names and "opt_apply" in names


def test_batchiter_emits_data_wait_spans():
    device.set_tracing(True)
    it = data_mod.BatchIter(lambda: iter([(1, 2), (3, 4)]))
    assert list(it) == [(1, 2), (3, 4)]
    names = [r["name"] for r in trace.records()]
    assert names.count("data_wait") >= 2


# ---------------------------------------------------------------------------
# Device-trace sink: an enabled span is a jax.profiler.TraceAnnotation
# ---------------------------------------------------------------------------
@pytest.fixture
def annotations(monkeypatch):
    """Stand in for `jax.profiler.TraceAnnotation`; the log holds
    (what, name) in the order things happened."""
    log = []

    class Fake:
        def __init__(self, name):
            self.name = name
            log.append(("new", name))

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))
            return False

    monkeypatch.setattr(trace, "_ANNOTATION", Fake)
    return log


@pytest.mark.parametrize("how", ["clean", "exception", "mismatched"])
def test_enabled_span_is_exactly_one_annotation(annotations, how):
    device.set_tracing(True)
    if how == "clean":
        with trace.span("work", rows=3):
            assert annotations == [("new", "singa:work"),
                                   ("enter", "singa:work")]
    elif how == "exception":
        with pytest.raises(KeyError):
            with trace.span("work", rows=3):
                raise KeyError("boom")
    else:  # a generator's teardown: the outer span leaves first
        outer, inner = trace.span("work", rows=3), trace.span("inner")
        outer.__enter__()
        inner.__enter__()
        outer.__exit__(None, None, None)
        inner.__exit__(None, None, None)
    # the span's args never reach the annotation's name
    for name in {n for _, n in annotations}:
        assert [w for w, n in annotations if n == name] == [
            "new", "enter", "exit"]
    assert {n for _, n in annotations} == (
        {"singa:work"} if how != "mismatched"
        else {"singa:work", "singa:inner"})
    assert {r["name"] for r in trace.records()} == {
        n[len("singa:"):] for _, n in annotations}


def test_step_span_and_record_span_sinks(annotations):
    """`step_span` is a span like any other; `record_span` has its
    endpoints in the past and stays in the ring only."""
    device.set_tracing(True)
    with trace.step_span(7):
        trace.record_span("queue_wait", 1.0, 2.0)
    assert annotations == [("new", "singa:step"), ("enter", "singa:step"),
                           ("exit", "singa:step")]
    assert {r["name"] for r in trace.records()} == {"step", "queue_wait"}


def test_disabled_span_makes_no_annotation_and_allocates_nothing(
        annotations):
    """The PR 5 pin with the sink in place: disabled, `span()` is
    the shared null context: no annotation object, and no allocation
    that grows with the number of calls (the smallest per-call leak,
    a 24-byte object, would be 48 KB over 2000 calls)."""
    import tracemalloc

    assert not trace.enabled()
    assert trace.span("decode.step.dispatch", steps=8) is trace._NULL
    N = 2000
    only_trace = tracemalloc.Filter(True, "*trace.py")
    rounds = []
    tracemalloc.start()
    try:
        for _ in range(3):
            for _ in range(50):  # warm frames/freelists
                with trace.span("decode.step.readback", steps=1):
                    pass
            before = tracemalloc.take_snapshot().filter_traces(
                [only_trace])
            for _ in range(N):
                with trace.span("decode.step.readback", steps=1):
                    pass
            after = tracemalloc.take_snapshot().filter_traces(
                [only_trace])
            rounds.append(sum(
                d.size_diff for d in after.compare_to(before, "lineno")
                if d.size_diff > 0))
    finally:
        tracemalloc.stop()
    assert annotations == []
    assert trace.records() == []
    assert min(rounds) < N // 2, (
        f"disabled span allocates per call: {rounds} bytes per "
        f"{N}-call round")


def test_spans_of_a_worker_thread_reach_the_profilers_host_plane(
        tmp_path):
    """Live, on the CPU backend: with a profiler session running, the
    spans a worker thread opens come back from the trace's /host:CPU
    plane under "singa:<name>", in order, nested as they were."""
    import glob

    import jax
    from jax.profiler import ProfileData

    device.set_tracing(True)

    def work():
        for _ in range(3):
            with trace.span("outer", rows=1):
                with trace.span("inner"):
                    np.dot(_X.T, _X)

    jax.profiler.start_trace(str(tmp_path))
    try:
        t = threading.Thread(target=work)
        t.start()
        t.join(30)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events if e.name.startswith("singa:")]
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines]
    (mine,) = [evs for evs in lines if evs]   # one thread, one line
    mine.sort(key=lambda e: (e[1], -e[2]))
    assert [e[0] for e in mine] == ["singa:outer", "singa:inner"] * 3
    for outer, inner in zip(mine[::2], mine[1::2]):
        assert outer[1] <= inner[1] and inner[2] <= outer[2]
    # the ring holds the same six, on perf_counter
    assert [r["name"] for r in trace.records()] == ["inner", "outer"] * 3


@pytest.mark.parametrize("tracer", [False, True])
def test_step_phases_reach_a_profiler_session_with_the_tracer_off(
        tmp_path, tracer):
    """Live, on the CPU backend: a compiled step's four phases are in
    a running profiler session's /host:CPU plane whether or not the
    program's tracer is on, nested as `_JitStep.__call__` runs them,
    and in the ring only while it is on."""
    import glob

    import jax
    from jax.profiler import ProfileData

    device.set_tracing(tracer)
    m, tx, ty = _build(use_graph=True)
    m(tx, ty)                           # compile outside the session
    trace.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            _, loss = m(tx, ty)
        float(loss.to_numpy())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    mine = sorted(
        ((e.name, e.start_ns, e.start_ns + e.duration_ns)
         for plane in ProfileData.from_file(path).planes
         if plane.name == "/host:CPU" for line in plane.lines
         for e in line.events if e.name.startswith("singa:step.")),
        key=lambda e: (e[1], -e[2]))
    assert [e[0] for e in mine] == [
        "singa:step.call", "singa:step.place", "singa:step.enqueue",
        "singa:step.bind"] * 3
    for i in range(0, 12, 4):
        call, rest = mine[i], mine[i + 1:i + 4]
        assert all(call[1] <= e[1] and e[2] <= call[2] for e in rest)
        assert all(a[2] <= b[1] for a, b in zip(rest, rest[1:]))
    ring = [r["name"] for r in trace.records()]
    assert ring == (["step.place", "step.enqueue", "step.bind",
                     "step.call"] * 3 if tracer else [])


def test_phase_is_a_span_only_while_the_tracer_is_on():
    with trace.phase("step.call"):
        pass
    assert trace.records() == []
    assert trace.span("x") is trace.span("y")      # the shared null
    device.set_tracing(True)
    with trace.phase("step.call"):
        pass
    assert [r["name"] for r in trace.records()] == ["step.call"]


# ---------------------------------------------------------------------------
# Metrics JSONL
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["eager", "graph", "accum2", "mesh"])
def test_metrics_one_schema_stable_record_per_step(tmp_path, mode):
    """Exactly one record per train step with a stable key set —
    including under grad_accum=n and on the 8-device mesh path."""
    device.set_tracing(True)
    kw = {"eager": dict(use_graph=False),
          "graph": dict(use_graph=True),
          "accum2": dict(use_graph=True, grad_accum=2),
          "mesh": dict(use_graph=True, grad_accum=2,
                       mesh=create_mesh({"data": 8}))}[mode]
    m, tx, ty = _build(**kw)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    log_path = str(tmp_path / "metrics.jsonl")
    with trace.MetricsLogger(log_path) as ml:
        resilience.run_resumable(m, mgr, lambda s: (tx, ty), 4,
                                 save_every=2, metrics=ml)
    recs = trace.read_metrics(log_path)
    assert [r["step"] for r in recs] == [1, 2, 3, 4]
    assert len({tuple(sorted(r)) for r in recs}) == 1, "schema drifted"
    for r in recs:
        assert r["schema"] == trace.SCHEMA_VERSION
        assert isinstance(r["loss"], float)
        assert r["examples_per_sec"] > 0
        assert r["dispatch_s"] is None or r["dispatch_s"] >= 0
    if mode in ("accum2", "mesh"):
        assert recs[-1]["accum"]["n"] == 2
        assert recs[-1]["accum"]["accum_steps"] >= 1
    names = {r["name"] for r in trace.records()}
    assert "checkpoint_restore" in names and "checkpoint_save" in names
    if mode == "mesh":
        assert "step.place" in names
    # step spans: one per executed step
    assert sum(1 for r in trace.records() if r["name"] == "step") == 4


def test_metrics_logger_without_tracer_still_schema_stable(tmp_path):
    """Tracing off: timing decomposition is None but the record schema
    and the one-per-step contract hold."""
    m, tx, ty = _build(use_graph=False)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    log_path = str(tmp_path / "metrics.jsonl")
    with trace.MetricsLogger(log_path) as ml:
        resilience.run_resumable(m, mgr, lambda s: (tx, ty), 3,
                                 save_every=3, metrics=ml)
    recs = trace.read_metrics(log_path)
    assert [r["step"] for r in recs] == [1, 2, 3]
    assert len({tuple(sorted(r)) for r in recs}) == 1
    for r in recs:
        assert r["data_wait_s"] is None and r["dispatch_s"] is None
        assert r["step_s"] > 0 and r["examples_per_sec"] > 0
    assert trace.records() == []  # tracer stayed a no-op


def test_metrics_cache_deltas_are_deltas(tmp_path):
    log_path = str(tmp_path / "m.jsonl")
    with trace.MetricsLogger(log_path) as ml:
        m, tx, ty = _build(use_graph=False)
        m(tx, ty)
        r1 = ml.log_step(1, loss=0.0, examples=32, step_s=0.1)
        m(tx, ty)
        r2 = ml.log_step(2, loss=0.0, examples=32, step_s=0.1)
    # the fused optimizer dispatches exactly once per eager step: both
    # records carry a DELTA of 1 (a cumulative value would read 2 in
    # the second record)
    c1, c2 = r1["cache"]["fused_opt"], r2["cache"]["fused_opt"]
    assert c1["hits"] + c1["misses"] == 1
    assert c2["hits"] + c2["misses"] == 1


def test_metrics_cache_gauges_are_absolute(tmp_path):
    """Live-state gauges (slots_in_use, queue_depth, ring_size, LRU
    size, …) are NOT counters: occupancy dropping between records
    must not render as a negative delta. `_GAUGE_KEYS` fields pass
    through the cache-delta transform absolute."""
    d = stats.decode_stats()
    saved = (d.slots, d.slots_in_use)
    log_path = str(tmp_path / "m.jsonl")
    try:
        with trace.MetricsLogger(log_path) as ml:
            d.slots, d.slots_in_use = 8, 6
            r1 = ml.log_step(1, loss=0.0, step_s=0.1)
            d.slots_in_use = 2  # drained: a delta would read -4
            r2 = ml.log_step(2, loss=0.0, step_s=0.1)
    finally:
        d.slots, d.slots_in_use = saved
    assert r1["cache"]["decode"]["slots_in_use"] == 6
    assert r2["cache"]["decode"]["slots_in_use"] == 2
    assert r1["cache"]["decode"]["slots"] == 8
    assert r2["cache"]["decode"]["slots"] == 8
    # the trace ring rides the same rule: capacity is config, not a
    # one-record pulse that deltas to zero afterwards
    assert (r2["cache"]["trace"]["ring_capacity"]
            == r1["cache"]["trace"]["ring_capacity"] > 0)
    assert r2["cache"]["trace"]["ring_size"] >= 0


def test_metric_registers_into_metrics_logger(tmp_path):
    log_path = str(tmp_path / "m.jsonl")
    ml = trace.MetricsLogger(log_path)
    metric.Accuracy().register(ml, "acc")
    logits = np.array([[2.0, 1.0], [0.0, 3.0]], np.float32)
    labels = np.array([0, 0], np.int32)
    rec = ml.log_step(1, loss=0.5, outputs=logits, labels=labels)
    assert rec["metrics"]["acc"] == 0.5
    rec2 = ml.log_step(2, loss=0.4)  # no eval data this step
    assert rec2["metrics"]["acc"] is None
    assert set(rec) == set(rec2)  # schema holds either way
    ml.close()
    assert [r["step"] for r in trace.read_metrics(log_path)] == [1, 2]


def test_killed_run_leaves_parseable_log(tmp_path):
    """SIGKILL mid-write: every flushed record parses; the partial
    trailing line is skipped, not raised on (the fit_resumable crash
    contract)."""
    log_path = str(tmp_path / "crash.jsonl")
    code = textwrap.dedent(f"""
        import os, signal
        from singa_tpu import trace
        ml = trace.MetricsLogger({log_path!r})
        for i in range(5):
            ml.log_step(i, loss=float(i), examples=4, step_s=0.01)
        # simulate the kill landing mid-line: partial record, no newline
        ml._f.write(b'{{"step": 5, "loss": 0.')
        ml._f.flush()
        os.kill(os.getpid(), signal.SIGKILL)
    """)
    proc = subprocess.run([sys.executable, "-c", code], cwd=_ROOT,
                          capture_output=True, timeout=240)
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
    recs = trace.read_metrics(log_path)
    assert [r["step"] for r in recs] == [0, 1, 2, 3, 4]
    assert all(isinstance(r["loss"], float) for r in recs)


def test_read_metrics_missing_file_is_empty():
    assert trace.read_metrics("/nonexistent/nowhere.jsonl") == []


@pytest.mark.parametrize("kind", ["context", "state", "a_kind_no_file_names"])
def test_metrics_slab_bytes_gauges_are_absolute_whatever_the_kind(tmp_path,
                                                                  kind):
    """`cache_bytes_<kind>` is a gauge for every kind of slab entry a
    model states (`ServingEngine._note_slab_bytes` adds the kinds it is
    given): matched by prefix, so a slab that does not grow between
    two records reads its bytes in both, not a delta of 0."""
    d = stats.decode_stats()
    saved = dict(d.cache_bytes)
    try:
        with trace.MetricsLogger(str(tmp_path / "m.jsonl")) as ml:
            d.cache_bytes = {**saved, kind: 4096}
            r1 = ml.log_step(1, loss=0.0, step_s=0.1)
            r2 = ml.log_step(2, loss=0.0, step_s=0.1)
    finally:
        d.cache_bytes = saved
    assert r1["cache"]["decode"]["cache_bytes_" + kind] == 4096
    assert r2["cache"]["decode"]["cache_bytes_" + kind] == 4096
