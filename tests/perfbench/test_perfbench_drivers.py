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
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, run.wrong
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
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
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
    assert "tolerance 0.001" in run.notes["reference_check"]
    assert not any("reference" in w or "differ" in w for w in run.wrong)
    assert line["device"]["count"] == 8
    assert line["metrics"]["collective_exposed_pct"]["value"] == \
        pytest.approx(10.0)


def test_a_compile_in_the_window_makes_the_run_incorrect(monkeypatch):
    cell, config, workload = cell_mod.load_cell("toy-train-lm", TOY)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=1.0, trace=False,
                       end_to_end={"train_items_per_s": 1.0, "setup_s": 1.0},
                       compiles_in_window=2)
    line = run_mod.result_line(run, jax.devices()[:1], "host-loop", TOY)
    assert line["correct"] is False


def test_a_metric_the_driver_did_not_measure_is_an_error():
    cell, config, workload = cell_mod.load_cell("toy-train-lm", TOY)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=1.0, trace=False, end_to_end={"setup_s": 1.0})
    with pytest.raises(KeyError, match="train_items_per_s"):
        run_mod.result_line(run, jax.devices()[:1], "host-loop", TOY)
