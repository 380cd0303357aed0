"""What ISSUE 27 adds to the benchmark, on the CPU: the serving driver
drives the hybrid window/full mixture-of-experts model at toy size
through to a result line (the toy cell lives under
tests/perfbench/toy_moe/, a root of its own: never a cell, never
reachable from the command); the new per-layer readers read counters
and a trace given as tuples; the new cells' files say what ISSUE 27
asked of them."""
import os
import time

import jax
import pytest

from perfbench import run as run_mod
from perfbench.harness import cell as cell_mod
from perfbench.harness import (moe_trace, opcount, opcount_moe, peaks,
                               profiler)
from perfbench.layer_metrics import (expert_load_max_over_mean,
                                     moe_experts_roofline_pct, moe_step_ms)
from perfbench.reference import mimo_v2_control
from test_perfbench_contract import (check_lists_grow_at_their_end,
                                     config_of, module_of)
from test_perfbench_drivers import FakeDeviceTrace, _meter, policies  # noqa: F401

TOY = os.path.join(os.path.dirname(__file__), "toy_moe")
V5E = peaks.for_kind("TPU v5 lite")


def _drive(trace, monkeypatch):
    monkeypatch.setattr(profiler, "DeviceTrace", FakeDeviceTrace)
    cell, config, workload = cell_mod.load_cell("toy-moe-serve", TOY)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=1.0, trace=trace, seed=2147483905,
                       t_process_start=time.perf_counter(), meter=_meter(),
                       peaks=V5E)
    driver = cell_mod.module("drivers", workload["driver"])
    driver.run(run)
    return run, run_mod.result_line(run, jax.devices()[:1],
                                    driver.UNATTRIBUTED_GAP, TOY)


def test_the_serving_driver_serves_the_model_and_the_reference_agrees(
        monkeypatch, policies):  # noqa: F811
    run, line = _drive(False, monkeypatch)
    assert line["correct"] is True, run.wrong
    assert set(line["metrics"]) == {"out_tokens_per_s", "tpot_p50_ms",
                                    "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "margin 0.001" in run.notes["reference_check"]


def test_a_traced_run_reports_the_counter_metrics(monkeypatch,
                                                  policies):  # noqa: F811
    """No device trace file on the CPU: the readers of device time find
    nothing and are left out; the counter's reader reads."""
    run, line = _drive(True, monkeypatch)
    assert line["correct"] is True, run.wrong
    m = line["metrics"]
    assert 1.0 <= m["expert_load_max_over_mean"]["value"] <= 4.0
    assert not {"moe_step_ms", "attn_full_step_ms", "attn_window_step_ms",
                "moe_experts_roofline_pct"} & set(m)
    assert {"tokens_per_step", "decode_step_ms_p50", "prefill_ms_p50",
            "compiles_in_window", "serve_device_idle_pct"} <= set(m)
    d = run.counters["decode"]
    assert d["moe_assignments_local"] > 0 and d["moe_experts_touched"] > 0


# -- the device-time readers, on tuples -----------------------------------------
def _config(root):
    return config_of(root, "mimo-v2.5")


def _parts(root):
    """The configuration's own `step_parts`, at the cell's 128 slots."""
    return moe_trace.part_patterns(_config(root)["step_parts"], 128)


GATE = ("%fusion.7 = bf16[16,128,2048]{2,1,0} fusion(bf16[128,4096]{1,0} %x, "
        "bf16[16,4096,2048]{2,1,0} %W_g), kind=kOutput, calls=%f.7")
DOWN = ("%fusion.9 = bf16[128,4096]{1,0} fusion(bf16[128,32768]{1,0} %a, "
        "bf16[32768,4096]{1,0} %bitcast.3), kind=kOutput, calls=%f.9")
FULL = ("%fusion.3 = f32[128,4,16,4096]{3,2,1,0} fusion(bf16[128,4,16,192]"
        "{3,2,1,0} %q, bf16[128,4,192,4096]{3,2,1,0} %k), kind=kOutput")
RING = ('%custom-call.5 = bf16[128,8,192,128]{3,2,1,0} custom-call(s32[128]{0}'
        ' %at, bf16[128,8,192,128]{3,2,1,0} %c, bf16[128,8,192,1]{3,2,1,0} %n)'
        ', custom_call_target="tpu_custom_call"')
OTHER = "%fusion.1 = bf16[128,13568]{1,0} fusion(bf16[128,4096]{1,0} %x)"
WHILE = ("%while.2 = (s32[], bf16[16,4096,2048]{2,1,0}, bf16[128,4,192,4096]"
         "{3,2,1,0}) while((s32[], bf16[16,4096,2048]{2,1,0}) %t), body=%b")


def test_decode_steps_and_their_parts_are_found_by_module_and_shape(root):
    mods = [("jit_slot_step(123)", 0, 1000), ("jit_prefill_rows(5)", 1000,
                                              3000),
            ("jit_slot_scan_4(77)", 3000, 8000), ("jit_slot_step(123)",
                                                  9500, 10500)]
    ops = [(GATE, 100, 300), (DOWN, 300, 400), (FULL, 400, 700),
           (RING, 700, 750), (OTHER, 750, 900),
           (GATE, 1500, 2500),                 # a prefill's: not a step's
           (WHILE, 3000, 8000), (GATE, 3100, 3500), (FULL, 3500, 3900),
           (GATE, 9600, 9700)]                 # its program ends outside
    red = moe_trace.reduce(ops, mods, _parts(root), 0, 10000)
    assert list(red["seconds"]) == ["moe_experts", "attn_full", "attn_window"]
    assert red["steps"] == 5                   # 1 + a block of 4
    assert red["seconds"]["moe_experts"] == pytest.approx(700e-9)
    assert red["seconds"]["attn_full"] == pytest.approx(700e-9)
    assert red["seconds"]["attn_window"] == pytest.approx(50e-9)
    assert red["step_seconds"] == pytest.approx(6000e-9)


def test_a_program_without_named_modules_leaves_nothing_to_read(root):
    mods = [("jit__lambda_(123)", 0, 1000)]
    red = moe_trace.reduce([(GATE, 100, 300)], mods, _parts(root), 0, 1000)
    assert red["steps"] == 0


@pytest.mark.parametrize("name", ["mimo-v2.5", "toy_moe"])
def test_the_files_parts_are_its_own_widths(root, name):
    """`step_parts` is written out, not derived, so hold it to the
    widths the same file gives the model: the held experts' matrices
    (and their [E * f, d] view, and a step's [E, slots, f] product),
    and [slots, key/value heads of the kind, ., .] for attention."""
    config = config_of(TOY if name == "toy_moe" else root, name)
    k = config["builder"]["kwargs"]
    e, d, f = k["held"][1], k["d_model"], k["d_ff_expert"]
    assert config["step_parts"] == {
        "moe_experts": [rf"\[{e},{d},{f}\]", rf"\[{e},{f},{d}\]",
                        rf"\[{e * f},{d}\]", rf"\[{e},{{slots}},{f}\]"],
        "attn_full": [rf"\[{{slots}},{k['kv_heads_full']},\d+,\d+\]"],
        "attn_window": [rf"\[{{slots}},{k['kv_heads_window']},\d+,\d+\]"]}
    assert list(config["step_parts"]) == ["moe_experts", "attn_full",
                                          "attn_window"]
    # `{slots}` is the one placeholder, filled as a number
    rx = moe_trace.part_patterns(config["step_parts"], 64)
    assert rx["attn_full"].search(f"f32[64,{k['kv_heads_full']},16,4096]")
    assert not rx["attn_full"].search(
        f"f32[128,{k['kv_heads_full']},16,4096]")


def test_a_run_reads_the_parts_its_configuration_gives(root):
    """`{slots}` comes from the engine settings, which the traffic
    file may override (the pool is the next power of two); a part the
    configuration lacks, or a configuration without the section, reads
    None."""
    run = _run_with(root, {"steps": 10, "step_seconds": 1.0, "seconds": {
        "moe_experts": 0.5, "attn_full": 0.0}}, {})
    assert moe_trace.step_ms(run, "moe_experts") == pytest.approx(50.0)
    assert moe_trace.step_ms(run, "attn_full") is None      # nothing ran
    assert moe_trace.step_ms(run, "conv_state") is None     # no such part
    assert moe_trace.patterns_of(run)["attn_window"].pattern \
        == r"(?:\[128,8,\d+,\d+\])"
    run.workload["engine"] = {"max_sessions": 48}
    assert moe_trace.patterns_of(run)["attn_window"].pattern \
        == r"(?:\[64,8,\d+,\d+\])"
    # no section: nothing to read, whatever the trace holds
    bare = _run_with(root, None, {})
    del bare.moe_trace, bare.config["step_parts"]
    bare.device_trace, bare.trace_window_ns = object(), (0, 1)
    assert moe_trace.of_run(bare) is None
    assert moe_step_ms.read(bare) is None
    assert moe_experts_roofline_pct.read(bare) is None


def _run_with(root, red, counters):
    run = cell_mod.Run(cell={"name": "x"}, config=_config(root), workload={},
                       seconds=30.0, trace=True, peaks=V5E)
    run.moe_trace = red
    run.counters["decode"] = counters
    return run


def test_the_roofline_share_is_the_touched_weights_over_the_time_taken(root):
    """64 assignments on 15.6 of 16 experts a layer, 6 layers, a step:
    the need is those experts' 50 MB once (memory-bound), and a kernel
    that streams all 96 expert-layers at the peak reads 97 %."""
    steps = 100
    weights = 96 * 3 * 4096 * 2048 * 2
    red = {"steps": steps, "step_seconds": 2.0, "seconds": {
        "moe_experts": steps * weights / V5E["hbm_bytes_per_s"],
        "attn_full": 0.4, "attn_window": 0.0}}
    run = _run_with(root, red, {"decode_steps": 1000,
                          "moe_assignments_local": 1000 * 6 * 64,
                          "moe_experts_touched": 1000 * 93.5,
                          "moe_expert_load_max": 1000 * 6 * 9})
    share = moe_experts_roofline_pct.read(run)
    assert 97.0 < share < 98.0
    assert "memory-bound" in run.notes["moe_experts_roofline"]
    assert moe_step_ms.read(run) == pytest.approx(
        1e3 * weights / V5E["hbm_bytes_per_s"])
    # the fullest of 16 experts holds 9 where the mean holds 4
    assert expert_load_max_over_mean.read(run) == pytest.approx(9 / 4)
    assert moe_experts_roofline_pct.read(_run_with(root, None, {})) is None
    assert expert_load_max_over_mean.read(_run_with(root, None, {})) is None


def test_the_control_tier_comes_out_not_correct(policies):  # noqa: F811
    """The margin's two sides through the harness's own comparison: the
    served tokens pass it, the greedy choice of the reference computed
    one precision below the configuration's (bfloat16 under the toy's
    float32) does not. The chip's readings at the published widths are
    in PERF.md; this is the same code at toy size."""
    cell, config, workload = cell_mod.load_cell("toy-moe-serve", TOY)
    out = mimo_v2_control.run(cell_mod.Run(
        cell=cell, config=config, workload=workload, seconds=0.0,
        trace=False, seed=2147483905, t_process_start=time.perf_counter()))
    assert out["streams"] == 2 and out["lower"] == "bfloat16"
    assert out["served_correct"] and out["served_worst"] <= 0.001
    assert not out["control_correct"] and out["control_worst"] > 0.01


def test_opcount_moe_counts_what_is_needed(root):
    ops, nbytes = opcount_moe.expert_products(64, 16, 4096, 2048, 2)
    assert ops == 64 * 3 * 2 * 4096 * 2048
    assert nbytes == (16 * 3 * 4096 * 2048 + 64 * 2 * 4096) * 2
    nbytes_of_64 = nbytes
    assert opcount.roofline_seconds(ops, nbytes, V5E)[1] == "memory"
    # 4,096 rows: 2,048 assignments on the same 16 experts are compute's
    ops, nbytes = opcount_moe.expert_products(16 * 2048, 16, 4096, 2048, 2)
    assert opcount.roofline_seconds(ops, nbytes, V5E)[1] == "compute"
    # the configuration names this function and the published widths
    spec = _config(root)["opcount"]
    assert getattr(opcount_moe, spec["function"])(64, 16, **spec["kwargs"]) \
        == (64 * 3 * 2 * 4096 * 2048, nbytes_of_64)


# -- the new cells' files say what was asked ---------------------------------------
def test_the_serving_cell_is_the_issues(root):
    cell, config, w = cell_mod.load_cell("mimo-v2.5-serve-mixedlen", root)
    assert (w["loop"], w["clients"]) == ("closed", 128)
    assert w["prompt_len"] == {"dist": "lognormal", "median": 384,
                               "sigma": 1.0, "min": 64, "max": 2048}
    assert w["output_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.5, "min": 64, "max": 512}
    assert w["first_output_scale"] == "uniform" and w["grace_s"] == 0
    engine = {**config["serve"]["engine"], **w.get("engine", {})}
    # one prompt a prefill: a cohort pads to its longest member's
    # bucket, which at these widths costs more than it saves (the
    # configuration's `assumed` gives the chip's numbers)
    assert engine == {"max_sessions": 128, "max_new_tokens": 512,
                      "prefill_batch": 1}
    k = config["builder"]["kwargs"]
    assert config["builder"]["args"] == [config["vocab_size"]] == [19072]
    assert k["param_dtype"] == config["serve"]["compute_dtype"] == "bfloat16"
    # the longest request fits the positions served
    assert 2048 + 512 <= k["max_len"] == config["max_position_embeddings"]
    assert k["held"] == [0, config["n_routed_experts"]] == [0, 16]
    assert k["n_experts"] == config["published"]["n_routed_experts"] == 256
    assert k["layer_pattern"] == config["hybrid_layer_pattern"]
    assert k["moe_layers"] == config["moe_layer_freq"]
    assert len(k["layer_pattern"]) == config["num_hidden_layers"] == 7
    # every width is the published one
    for ours, theirs in (("d_model", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("head_dim", "head_dim"),
                         ("v_head_dim", "v_head_dim"),
                         ("kv_heads_full", "num_key_value_heads"),
                         ("kv_heads_window", "swa_num_key_value_heads"),
                         ("window", "sliding_window"),
                         ("d_ff", "intermediate_size"),
                         ("d_ff_expert", "moe_intermediate_size"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("rope_theta_full", "rope_theta"),
                         ("rope_theta_window", "swa_rope_theta"),
                         ("value_scale", "attention_value_scale"),
                         ("norm_eps", "layernorm_epsilon")):
        assert k[ours] == config[theirs], ours
    assert k["rotary_dim"] == int(config["partial_rotary_factor"]
                                  * config["head_dim"])
    assert {**k, **config["reference"]["kwargs"]} == k


def counted_by(config, root):
    """The step counters the configuration's model returns beside its
    logits (`DecodeLM.step_counter_names`, on the class the `builder`
    names)."""
    model = cell_mod.resolve_callable(config["builder"]["callable"], root)
    return set(model.step_counter_names)


def check_the_hybrid_cell_reports_what_the_decode_cell_does(root):
    """A per-layer metric that `gpt2-serve-decode` reports from the
    program's spans or counters or the host's clock, this cell reports
    too: `serve.py` is the same under both models. The one exception is
    in the reader's own file, so a new reader brings its own: it names
    (`COUNTERS`) a step counter that the hybrid model does not count,
    as `attn_rung_read_pct` does the blocks of `decode_attend`
    (`HybridWindowMoELM._attend_slab` reads the whole rung and counts
    none). A reader of the device trace finds one model's operations,
    so which cells it lists is its own affair."""
    of = {name: {m["name"]: m
                 for m in cell_mod.metrics_for(name, "per_layer", root)}
          for name in ("gpt2-serve-decode", "mimo-v2.5-serve-mixedlen")}
    _, config, _ = cell_mod.load_cell("mimo-v2.5-serve-mixedlen", root)
    counted = counted_by(config, root)
    for name in sorted(set(of["gpt2-serve-decode"])
                       - set(of["mimo-v2.5-serve-mixedlen"])):
        if of["gpt2-serve-decode"][name]["source"] == "device_trace":
            continue
        reader = module_of(root, "layer_metrics", name)
        assert set(getattr(reader, "COUNTERS", ())) - counted, (
            f"{name}: list mimo-v2.5-serve-mixedlen too, or name in the "
            "reader's COUNTERS the step counter its model does not count")


def test_the_new_cell_joins_the_decode_cells_metrics_and_only_grows_lists(
        root):
    """For any number of cells: a metric's list names cells in
    BENCHMARK.json's own order, so lists only grow at their end; and
    this cell reports every per-layer metric `gpt2-serve-decode`
    reports, but for those whose reader cannot read its program. (That
    few cells take four chips is the contract test's.)"""
    check_lists_grow_at_their_end(cell_mod.benchmark(root), root)
    check_the_hybrid_cell_reports_what_the_decode_cell_does(root)
