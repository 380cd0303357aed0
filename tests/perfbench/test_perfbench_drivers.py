"""Each driver's functions in-process at toy size on the CPU, through
to a result line whose key set is exactly the contract's. The toy
cells live under tests/perfbench/toy/ (a root of their own with its
own BENCHMARK.json): never a cell, never reachable from the command."""
import os
import time

import jax
import pytest

from perfbench import run as run_mod
from perfbench.harness import cell as cell_mod
from perfbench.harness import compile_meter, peaks, profiler, xplane

TOY = os.path.join(os.path.dirname(__file__), "toy")
_METER = []


def _meter():
    if not _METER:        # listeners cannot be removed: one per process
        _METER.append(compile_meter.CompileMeter())
    return _METER[0]


# a flash-attention call as the trace names it, at the toy LM's shapes
# (batch 4 x 2 heads, 16 positions, head size 16)
ATTN_CALL = ('%jvp__.3 = bf16[8,16,16]{2,1,0} custom-call(bf16[8,16,16]{2,1,0} '
             '%p), custom_call_target="tpu_custom_call"')


class FakeDeviceTrace:
    """Stands in for the profiler on the CPU: a two-chip trace with one
    idle gap, so the trace-reading metrics have something to read."""

    def __init__(self, run):
        self.run = run

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.run.device_trace = xplane.Trace(
            devices={0: [("fusion.1", 0, 600), ("all-reduce.1", 700, 900)],
                     1: [(ATTN_CALL, 0, 500), ("fusion.2", 500, 1000)]},
            host=[("bench:window", 0, 1000)])
        self.run.trace_window_ns = (0, 1000)
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


def _drive(name, trace, monkeypatch, seconds=1.0):
    monkeypatch.setattr(profiler, "DeviceTrace", FakeDeviceTrace)
    cell, config, workload = cell_mod.load_cell(name, TOY)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=seconds, trace=trace, seed=3,
                       t_process_start=time.perf_counter(), meter=_meter(),
                       peaks=peaks.for_kind("TPU v5 lite"))
    driver = cell_mod.module("drivers", workload["driver"])
    driver.run(run)
    line = run_mod.result_line(run, jax.devices()[:cell["chips"]],
                               driver.UNATTRIBUTED_GAP, TOY)
    return run, line


E2E = {"toy-train-lm": {"train_items_per_s", "setup_s"},
       "toy-serve-closed": {"out_tokens_per_s", "ttft_p90_ms", "tpot_p50_ms",
                            "setup_s"},
       "toy-serve-open": {"out_tokens_per_s", "tpot_p50_ms", "setup_s"}}


@pytest.mark.parametrize("name", sorted(E2E))
def test_untraced_run_reports_the_end_to_end_metrics(name, monkeypatch,
                                                     policies):
    run, line = _drive(name, False, monkeypatch)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True, run.wrong
    # each number `correct` was decided from, inside its limit
    compared = line["compared"]
    assert compared["compiles_in_window"] == {"value": 0, "limit": 0}
    if name == "toy-train-lm":
        assert set(compared) == {"compiles_in_window",
                                 "eval_logits_off_reference",
                                 "last_loss_under_first"}
        assert compared["last_loss_under_first"]["value"] \
            < compared["last_loss_under_first"]["limit"]
    else:
        assert set(compared) == {"compiles_in_window",
                                 "served_token_under_reference_best"}
    assert all(c["value"] <= c["limit"] for c in compared.values())
    assert set(line["metrics"]) == E2E[name]
    assert all(set(m) == {"value", "unit"} and m["value"] > 0
               for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert "reference_check" in run.notes


PER_LAYER = {
    "toy-train-lm": {"host_dispatch_ms_p50", "compiles_in_window",
                     "device_idle_pct", "attn_roofline_pct"},
    "toy-serve-closed": {"gen_late_p99_ms", "client_tpot_p90_ms",
                         "tokens_per_step",
                         "step_gap_ms_p50", "decode_step_ms_p50",
                         "prefill_ms_p50", "compiles_in_window",
                         "serve_device_idle_pct"},
    "toy-serve-open": {"gen_late_p99_ms", "client_ttft_p90_ms",
                       "client_tpot_p90_ms", "tokens_per_step",
                       "step_gap_ms_p50", "decode_step_ms_p50",
                       "prefill_ms_p50", "compiles_in_window",
                       "serve_device_idle_pct"}}


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_traced_run_reports_the_per_layer_metrics(name, monkeypatch,
                                                  policies):
    run, line = _drive(name, True, monkeypatch)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "compared"]
    assert line["correct"] is True, run.wrong
    # the CPU reports no memory peak: that reader finds nothing to read
    # and the harness leaves its metric out
    assert set(line["metrics"]) == PER_LAYER[name]
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["device"]["busy_s"] == pytest.approx(900e-9)
    b = line["breakdown"]
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 5
    assert b["idle_gaps"][0][0] == cell_mod.module(
        "drivers", run.workload["driver"]).UNATTRIBUTED_GAP


def test_data_parallel_training_checks_the_replicas(monkeypatch, policies):
    """ResNet-50 at 32x32 over the 8 virtual chips: the reference
    agrees with the program's eval logits, every parameter is the same
    on every chip, and the traced run reads the exposed collective."""
    run, line = _drive("toy-train-dp4", True, monkeypatch, seconds=0.5)
    assert "0 differ" in run.notes["replicas"]
    assert line["compared"]["parameters_differing_across_chips"] == {
        "value": 0, "limit": 0}
    assert "tolerance 0.001" in run.notes["reference_check"]
    assert not any("reference" in w or "differ" in w for w in run.wrong)
    assert line["device"]["count"] == 8
    assert line["metrics"]["collective_exposed_pct"]["value"] == \
        pytest.approx(10.0)


def test_the_share_of_the_rung_read_is_the_block_counters_quotient():
    """`attn_rung_read_pct` on a Run with the program's counters: 32
    rows x 12 layers on a rung of 8 blocks a step, a third of them
    read; nothing to read where the program counted no blocks."""
    reader = cell_mod.module("layer_metrics", "attn_rung_read_pct")
    cell, config, workload = cell_mod.load_cell("toy-serve-closed", TOY)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=1.0, trace=True)
    assert reader.read(run) is None
    run.counters["decode"] = {"decode_steps": 100, "attn_blocks_read": 0,
                              "attn_blocks_rung": 0}
    assert reader.read(run) is None
    run.counters["decode"].update(attn_blocks_read=100 * 32 * 12 * 8 // 3,
                                  attn_blocks_rung=100 * 32 * 12 * 8)
    assert reader.read(run) == pytest.approx(100 / 3)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        "kernels", "%", "out_tokens_per_s")
    # the counters it names are the ones it divides
    assert set(reader.COUNTERS) == {"attn_blocks_read", "attn_blocks_rung"}


def test_the_share_of_steps_that_came_back_as_tokens():
    """`steps_tokens_only_pct` on a Run with the program's counters: 100
    from equal counters, less where some steps brought their logits
    back; nothing to read from a program that does not count them."""
    reader = cell_mod.module("layer_metrics", "steps_tokens_only_pct")
    cell, config, workload = cell_mod.load_cell("toy-serve-closed", TOY)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=1.0, trace=True)
    assert reader.read(run) is None
    run.counters["decode"] = {"decode_steps": 971, "tokens_streamed": 9000}
    assert reader.read(run) is None         # the parent of PR 33
    run.counters["decode"]["decode_steps_tokens"] = 971
    assert reader.read(run) == 100.0
    run.counters["decode"]["decode_steps_tokens"] = 600
    assert reader.read(run) == pytest.approx(100 * 600 / 971)
    run.counters["decode"].update(decode_steps=0, decode_steps_tokens=0)
    assert reader.read(run) is None         # no step in the window
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        "serving control plane", "%", "out_tokens_per_s")


def test_a_compile_in_the_window_makes_the_run_incorrect(monkeypatch):
    cell, config, workload = cell_mod.load_cell("toy-train-lm", TOY)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=1.0, trace=False,
                       end_to_end={"train_items_per_s": 1.0, "setup_s": 1.0},
                       compiles_in_window=2)
    line = run_mod.result_line(run, jax.devices()[:1], "host-loop", TOY)
    assert line["correct"] is False
    assert line["compared"] == {"compiles_in_window": {"value": 2,
                                                       "limit": 0}}


def test_a_metric_the_driver_did_not_measure_is_an_error():
    cell, config, workload = cell_mod.load_cell("toy-train-lm", TOY)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=1.0, trace=False, end_to_end={"setup_s": 1.0})
    with pytest.raises(KeyError, match="train_items_per_s"):
        run_mod.result_line(run, jax.devices()[:1], "host-loop", TOY)


class _FakeReply:
    """What `Load` reads of a `ServeReply`: a token every `step_s` on
    the wall clock from its admission, `n` of them."""

    def __init__(self, n, step_s):
        self.n, self.step_s, self.t0 = n, step_s, time.perf_counter()
        self.t_reply = None

    @property
    def _stream(self):
        return range(min(self.n, int((time.perf_counter() - self.t0)
                                     / self.step_s)))

    def done(self):
        return len(self._stream) == self.n

    def result(self, timeout=None):
        return None


class _FakeEngine:
    """A slot pool that sheds as `ServingEngine.submit_decode` does:
    no free slot, `ServeOverloadError` with the hinted wait."""

    def __init__(self, slots, step_s=0.02, hint_ms=5.0):
        self.slots, self.step_s, self.hint_ms = slots, step_s, hint_ms
        self.live, self.attempts, self.shed = [], 0, 0

    def submit_decode(self, ids, n_new, **_):
        from singa_tpu import serve

        self.attempts += 1
        self.live = [r for r in self.live if not r.done()]
        if len(self.live) >= self.slots:
            self.shed += 1
            raise serve.ServeOverloadError("decode slot pool exhausted",
                                           retry_after_ms=self.hint_ms)
        self.live.append(_FakeReply(n_new, self.step_s))
        return self.live[-1]


def test_a_shed_request_comes_back_after_the_hint_and_is_late_not_failed():
    """A burst meets a full slot pool, as after a host that stood
    still: the engine sheds with its `retry_after_ms`, the client comes
    back when the hint is over, oldest first, and every request gets
    its answer; its times run from when it was DUE, so the wait is in
    the latency and `failed` stays 0."""
    from perfbench.drivers import serve as serve_driver

    cell, config, workload = cell_mod.load_cell("toy-serve-open", TOY)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=5.0, trace=False, seed=7)
    engine = _FakeEngine(slots=4)
    load = serve_driver.Load(run, engine, 97, 1.0)
    n = len(load.schedule)          # the lead-in's 2 and one block's 10
    assert n == 12
    # the host stood still for the window's first second: all are due
    load.t_open = t_open = time.perf_counter() - 1.0
    load.window = (t_open, t_open + 5.0)
    load.drive(time.perf_counter() + 0.003)
    assert len(load.live) == 4 and len(load.shed) == 8
    assert [r.req.index for r in load.shed] == list(range(4, 12))
    t_judged = time.perf_counter() + 2.0
    while load.unanswered(t_open - 1.0, t_open + 5.0):
        assert time.perf_counter() < t_judged, "the shed never came back"
        load.drive(time.perf_counter() + 0.05)
    assert not load.shed and not load.live and len(load.ended) == n
    assert all(r.error is None and r.seen == r.req.n_new
               for r in load.ended)
    came_back = [r for r in load.ended if r.sheds]
    assert len(came_back) == 8 and engine.shed == sum(
        r.sheds for r in came_back) >= 8
    # a retry waits out the hint: no storm of attempts at a full pool
    assert engine.attempts < n + 2.0 / (engine.hint_ms / 1e3)
    # admitted oldest first, and timed from when each was due
    order = sorted(came_back, key=lambda r: r.reply.t0)
    assert [r.req.index for r in order] == list(range(4, 12))
    assert all(r.t_first - r.t_due > 1.0 - r.req.due_s - 0.3
               and r.late == r.t_sent - r.t_due for r in came_back)
    serve_driver.summarize(run, load, t_open, time.perf_counter())
    assert (run.attempted, run.failed) == (10, 0)
    assert "8 shed at admission and sent again" in run.notes["serve"]


def test_a_request_never_answered_is_failed():
    """What `failed` still counts: a request the engine sheds to the
    end has no answer when judging ends."""
    from perfbench.drivers import serve as serve_driver

    cell, config, workload = cell_mod.load_cell("toy-serve-open", TOY)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=1.0, trace=False, seed=7)
    engine = _FakeEngine(slots=4, step_s=10.0)      # no slot ever frees
    load = serve_driver.Load(run, engine, 97, 1.0)
    load.t_open = t_open = time.perf_counter() - 1.0
    load.window = (t_open, t_open + 1.0)
    load.drive(time.perf_counter() + 0.1)
    assert len(load.shed) == 8 and load.unanswered(t_open, t_open + 1.0)
    serve_driver.summarize(run, load, t_open, time.perf_counter())
    # 10 due in the window; the 4 admitted ones are the lead-in's 2 and
    # the window's first 2, none finished; nothing to judge a rate from
    assert (run.attempted, run.failed) == (10, 10)
    assert any("nothing to judge" in w for w in run.wrong)


def test_a_host_that_stands_still_past_the_close_fails_no_request(
        monkeypatch, policies):
    """The whole open-loop run at toy size with the sending thread
    stopped from the middle of the window until after the close AND its
    grace: the arrivals it held back go out in one burst to a pool of 4
    slots, are shed, come back, and are waited for: late, not failed."""
    from perfbench.drivers import serve as serve_driver

    sweep, stood = serve_driver.Load._sweep, []

    def stalled(self, now):
        if not stood and now >= self.window[0] + 1.0:
            stood.append(now)
            time.sleep(3.3)                  # window 2.0 + grace 2.0 = 4.0
        return sweep(self, now)

    monkeypatch.setattr(serve_driver.Load, "_sweep", stalled)
    run, line = _drive("toy-serve-open", False, monkeypatch, seconds=2.0)
    assert stood and line["correct"] is True, run.wrong
    assert line["attempted"] == 20 and line["failed"] == 0
    note = run.notes["serve"]
    shed = int(note.split(" shed at admission")[0].split()[-1])
    assert shed > 0, note
    # the stall is in the numbers it belongs in: the generator was late
    assert max(run.samples["gen_late_s"]) > 2.0
    assert max(run.samples["ttft_s"]) > 2.0
