"""What ISSUE 32 adds to the benchmark, on the CPU: the serving driver
drives the short-convolution mixture-of-experts model at toy size
through to a result line (the toy cell lives under
tests/perfbench/toy_lfm2/, a root of its own: never a cell, never
reachable from the command); `conv_step_ms` reads a trace given as
tuples whose instruction texts are a traced chip run's; the new cell's
files say what ISSUE 32 asked of them; the control comes out not
correct."""
import os
import time

import jax
import pytest

from perfbench import run as run_mod
from perfbench.harness import cell as cell_mod
from perfbench.harness import moe_trace, opcount, opcount_moe, peaks, profiler
from perfbench.layer_metrics import (attn_full_step_ms, conv_step_ms,
                                     expert_load_max_over_mean,
                                     moe_experts_roofline_pct, moe_step_ms)
from perfbench.reference import lfm2_moe_control
from test_perfbench_contract import config_of
from test_perfbench_drivers import FakeDeviceTrace, _meter, policies  # noqa: F401

TOY = os.path.join(os.path.dirname(__file__), "toy_lfm2")
CELL = "lfm2-24b-a2b-serve-decode128"
# the catalog row `LFM2-24B-A2B` (the model-configs guide's
# architectures.jsonl, read when ISSUE 32 was written): its `source_url`
# and every key of its `config`, written out so that the test reads
# nothing outside the checkout
CATALOG_ROW = {
    "source_url": "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/"
                  "config.json",
    "config": {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
        "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536,
        "layer_types": (["conv", "conv", "full_attention", "conv"]
                        + ["conv", "conv", "full_attention", "conv"] * 9)}}
V5E = peaks.for_kind("TPU v5 lite")


def _toy_run(trace, seconds=1.0):
    cell, config, workload = cell_mod.load_cell("toy-lfm2-serve", TOY)
    return cell_mod.Run(cell=cell, config=config, workload=workload,
                        seconds=seconds, trace=trace, seed=2147483905,
                        t_process_start=time.perf_counter(), meter=_meter(),
                        peaks=V5E)


def _drive(trace, monkeypatch):
    monkeypatch.setattr(profiler, "DeviceTrace", FakeDeviceTrace)
    run = _toy_run(trace)
    driver = cell_mod.module("drivers", run.workload["driver"])
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
    assert line["compared"]["served_token_under_reference_best"][
        "value"] <= 0.001


def test_a_traced_run_reports_the_counter_metrics(monkeypatch,
                                                  policies):  # noqa: F811
    """No device trace file on the CPU: the readers of device time find
    nothing and are left out; the counter's reader reads, with every
    expert held (`held` [0, 16] in the toy's builder)."""
    from singa_tpu import stats

    run, line = _drive(True, monkeypatch)
    assert line["correct"] is True, run.wrong
    m = line["metrics"]
    assert 1.0 <= m["expert_load_max_over_mean"]["value"] <= 16.0
    assert not {"moe_step_ms", "attn_full_step_ms", "conv_step_ms",
                "moe_experts_roofline_pct"} & set(m)
    assert {"tokens_per_step", "decode_step_ms_p50", "prefill_ms_p50",
            "compiles_in_window", "serve_device_idle_pct"} <= set(m)
    d = run.counters["decode"]
    # 8 routed layers x 4 slots x 4 experts a token, every one local
    assert d["moe_assignments_local"] == 8 * 4 * 4 * d["decode_steps"]
    gauges = stats.cache_stats()["decode"]
    assert gauges["cache_bytes_state"] == 7 * 4 * 2 * 48 * 4
    assert gauges["cache_bytes_context"] > 0 == gauges["cache_bytes_ring"]


def test_the_control_tier_comes_out_not_correct(policies):  # noqa: F811
    """The margin's two sides through the harness's own comparison: the
    served tokens pass it, the greedy choice of the reference computed
    one precision below the configuration's (bfloat16 under the toy's
    float32) does not. The chip's readings at the published widths are
    in PERF.md; this is the same code at toy size."""
    out = lfm2_moe_control.run(_toy_run(False, seconds=0.0))
    assert out["streams"] == 2 and out["lower"] == "bfloat16"
    assert out["served_correct"] and out["served_worst"] <= 0.001
    assert not out["control_correct"] and out["control_worst"] > 0.01


# -- the device-time readers, on tuples ------------------------------------------
# instruction texts of `lfm2-24b-a2b-serve-decode128`'s traced run on the
# chip (PR 32), layouts left out: the gate and the up-and-down products
# of a routed layer, a step's scores over a context and its cache
# write (the Pallas call carries the scope's name), the fused q/k/v
# projection, a convolution's input projection, its taps over the
# states, its output projection (found by the gate c it multiplies
# in), and what belongs to no part: the head and the dense MLP
GATE = ("%fusion.323 = bf16[64,1536,128] fusion(bf16[64,2048,1536] "
        "%p__blocks___1___ffn____W_g__.1, bf16[128,2048] "
        "%get-tuple-element.130, f32[2048] %copy-done.50, f32[128] "
        "%add_rsqrt_fusion.19), kind=kOutput, calls=%fused_computation.379")
DOWN = ("%fusion.162 = (f32[128], bf16[128,2048]) fusion(bf16[128,2048] "
        "%get-tuple-element.130, bf16[64,1536,2048] "
        "%p__blocks___1___ffn____W_d__.1, bf16[64,2048,1536] "
        "%p__blocks___1___ffn____W_u__.1, bf16[64,1536,128] %fusion.323, "
        "bf16[64,128] %bitcast.71), kind=kOutput")
SCORES = ("%fusion.545 = (f32[128,8,4], f32[128,8,4,2048]) fusion("
          "pred[128,2048] %iota_compare_fusion, bf16[128,8,64,2048] "
          "%attn_full.4, bf16[128,8,4,32] %bitcast.706, bf16[128,8,4,32] "
          "%bitcast.708), kind=kOutput, calls=%fused_computation.672")
WRITE = ('%attn_full.5 = bf16[128,8,64,2048] custom-call(s32[128] '
         '%copy-done.89, bf16[128,8,64,2048] %c_1___v__.1, bf16[128,8,64,1] '
         '%copy.442), custom_call_target="tpu_custom_call"')
QKV = ("%convolution_bitcast_fusion.8 = bf16[128,1,3072] fusion("
       "bf16[2048,3072] %custom-call.33, bf16[128,2048] "
       "%get-tuple-element.126, f32[2048] %copy-done.81, f32[128] "
       "%add_rsqrt_fusion.20), kind=kOutput, calls=%fused_computation.294")
CONV_IN = ("%convolution_bitcast_fusion.6 = bf16[128,1,6144] fusion("
           "bf16[2048,6144] %p__blocks___0___op____W_in__.1, bf16[128,2048] "
           "%fusion.8, f32[2048] %p__blocks___0___ln1__.1, f32[128] "
           "%add_rsqrt_fusion.22), kind=kOutput")
TAPS = ("%fusion.134 = (f32[128,2048], f32[128,2048]) fusion("
        "bf16[128,2,2048] %custom-call.43, pred[128,2] %copy-done.49, "
        "f32[2048] %bitcast.732, bf16[128,2,2048] %custom-call.44), "
        "kind=kLoop")
CONV_OUT = ("%fusion.153 = (f32[128], bf16[128,2048]) fusion(bf16[128,2048] "
            "%fusion.8, bf16[2048,2048] %copy-done.16, f32[128,2048] "
            "%get-tuple-element.22, bf16[128,1,6144] "
            "%convolution_bitcast_fusion.6, f32[2048] %bitcast.741), "
            "kind=kOutput")
HEAD = ("%fusion.275 = f32[128,65536] fusion(bf16[65536,2048] %p__embed__.1, "
        "bf16[128,2048] %get-tuple-element.178, f32[2048] %copy-done.88, "
        "f32[128] %add_rsqrt_fusion.4), kind=kOutput")
DENSE = ("%fusion.156 = (f32[128], bf16[128,2048]) fusion(bf16[128,2048] "
         "%get-tuple-element.124, bf16[11776,2048] %custom-call.26, "
         "bf16[2048,11776] %p__blocks___0___ffn____W_u__.1, bf16[128,11776] "
         "%fusion.387), kind=kOutput")


def _config(root):
    return config_of(root, "lfm2-24b-a2b")


def test_conv_step_ms_is_read_from_a_trace_by_the_files_own_parts(root):
    mods = [("jit_slot_step(123)", 0, 1000),
            ("jit_prefill_rows(5)", 1000, 3000),
            ("jit_slot_scan_4(77)", 3000, 8000)]
    ops = [(CONV_IN, 0, 30), (TAPS, 30, 40), (CONV_OUT, 40, 50),
           (QKV, 50, 60), (WRITE, 60, 70), (SCORES, 70, 200),
           (GATE, 200, 500), (DOWN, 500, 900), (DENSE, 900, 920),
           (HEAD, 920, 990),
           (CONV_IN, 1500, 2500),              # a prefill's: not a step's
           (CONV_IN, 3000, 3100), (TAPS, 3100, 3140), (GATE, 3200, 4000)]
    config = _config(root)
    rx = moe_trace.part_patterns(config["step_parts"], 128)
    assert list(rx) == ["moe_experts", "attn_full", "short_conv"]
    red = moe_trace.reduce(ops, mods, rx, 0, 10000)
    assert red["steps"] == 5
    assert red["seconds"]["short_conv"] == pytest.approx(190e-9)
    assert red["seconds"]["attn_full"] == pytest.approx(150e-9)
    assert red["seconds"]["moe_experts"] == pytest.approx(1500e-9)
    run = cell_mod.Run(cell={"name": CELL}, config=config, workload={},
                       seconds=30.0, trace=True, peaks=V5E)
    run.moe_trace = red
    assert conv_step_ms.read(run) == pytest.approx(1e3 * 190e-9 / 5)
    assert attn_full_step_ms.read(run) == pytest.approx(1e3 * 150e-9 / 5)
    assert moe_step_ms.read(run) == pytest.approx(1e3 * 1500e-9 / 5)
    assert (conv_step_ms.LAYER, conv_step_ms.UNIT, conv_step_ms.MOVES) \
        == ("model math", "ms", "tpot_p50_ms")
    # a configuration without the part (the hybrid model's), or a run
    # without a trace, leaves the reader with nothing: the metric is
    # left out, as on the parent of the PR that added it
    mimo = config_of(root, "mimo-v2.5")
    other = cell_mod.Run(cell={"name": "x"}, config=mimo, workload={},
                         seconds=30.0, trace=True, peaks=V5E)
    other.moe_trace = moe_trace.reduce(
        ops, mods, moe_trace.part_patterns(mimo["step_parts"], 128), 0, 10000)
    assert conv_step_ms.read(other) is None
    bare = cell_mod.Run(cell={"name": CELL}, config=config, workload={},
                        seconds=30.0, trace=False, peaks=V5E)
    assert conv_step_ms.read(bare) is None


def test_the_roofline_share_with_every_expert_held(root):
    """128 rows x 4 experts = 512 assignments a layer over 64 experts,
    8 routed layers: the need is the touched experts' weights once
    (memory-bound), so a kernel that streams all 512 expert-layers at
    80 % of the peak while 410 are touched reads 64 %."""
    steps, touched = 100, 410
    config = _config(root)
    spec = config["opcount"]["kwargs"]
    one = 3 * spec["d_model"] * spec["d_ff_expert"] * spec["itemsize"]
    assert 512 * one == 9663676416                  # the 9.66 GB a step
    red = {"steps": steps, "step_seconds": 2.0, "seconds": {
        "moe_experts": steps * 512 * one / (0.8 * V5E["hbm_bytes_per_s"]),
        "attn_full": 0.2, "short_conv": 0.04}}
    run = cell_mod.Run(cell={"name": CELL}, config=config, workload={},
                       seconds=30.0, trace=True, peaks=V5E)
    run.moe_trace = red
    run.counters["decode"] = {"decode_steps": 1000,
                              "moe_assignments_local": 1000 * 8 * 512,
                              "moe_experts_touched": 1000 * touched,
                              "moe_expert_load_max": 1000 * 8 * 40}
    share = moe_experts_roofline_pct.read(run)
    assert 0.8 * 100 * touched / 512 < share < 0.8 * 100 * touched / 512 + 1
    assert "memory-bound" in run.notes["moe_experts_roofline"]
    # the fullest of 64 experts holds 40 rows where the mean holds 8
    assert expert_load_max_over_mean.read(run) == pytest.approx(5.0)
    ops, nbytes = getattr(opcount_moe, config["opcount"]["function"])(
        512, 64, **spec)
    assert opcount.roofline_seconds(ops, nbytes, V5E)[1] == "memory"


@pytest.mark.parametrize("name", ["lfm2-24b-a2b", "toy_lfm2"])
def test_the_files_parts_are_its_own_widths(root, name):
    """`step_parts` is written out from a traced run's instruction
    texts, not derived, so hold it to the widths the same file gives
    the model: the held experts' matrices (their [E * f, d] view, a
    step's [E, slots, f] product); [slots, key/value heads, ., .] and
    the fused q/k/v matrix for attention; the convolution's input
    projection [d, 3d], its result [slots, 1, 3d] (which the output
    projection's fusion takes the gate from), the states [slots, L-1,
    d] and the taps [L, d]."""
    config = config_of(TOY if name == "toy_lfm2" else root, name)
    k = config["builder"]["kwargs"]
    e, d, f = k["held"][1], k["d_model"], k["d_ff_expert"]
    qkv = (k["num_heads"] + 2 * k["kv_heads"]) * k["head_dim"]
    L = k["conv_L"]
    assert config["step_parts"] == {
        "moe_experts": [rf"\[{e},{d},{f}\]", rf"\[{e},{f},{d}\]",
                        rf"\[{e * f},{d}\]", rf"\[{e},{{slots}},{f}\]"],
        "attn_full": [rf"\[{{slots}},{k['kv_heads']},\d+,\d+\]",
                      rf"\[{d},{qkv}\]"],
        "short_conv": [rf"\[{d},{3 * d}\]", rf"\[{{slots}},1,{3 * d}\]",
                       rf"\[{{slots}},{L - 1},{d}\]", rf"\[{L},{d}\]"]}
    assert list(config["step_parts"]) == ["moe_experts", "attn_full",
                                          "short_conv"]
    assert config["opcount"]["kwargs"]["d_model"] == d
    assert config["opcount"]["kwargs"]["d_ff_expert"] == f


# -- the new cell's files say what was asked -------------------------------------
def test_the_serving_cell_is_the_issues(root):
    cell, config, w = cell_mod.load_cell(CELL, root)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("lfm2-24b-a2b", "serve-decode128", 1)
    assert len(cell["why"]) <= 200
    assert "9 of 40" in cell["why"] and "host" in cell["why"]
    assert cell in cell_mod.benchmark(root)["workloads"]
    assert (w["loop"], w["clients"]) == ("closed", 128)
    assert w["prompt_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.5, "min": 64, "max": 1024}
    assert w["output_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.5, "min": 64, "max": 512}
    assert w["first_output_scale"] == "uniform" and w["grace_s"] == 0
    assert w["trace_seconds"] == 4 and "engine" not in w
    engine = config["serve"]["engine"]
    assert engine["max_sessions"] == w["clients"] == 128
    assert engine["max_new_tokens"] == 512
    # chosen from chip readings of 1, 2 and 4 (the file's `assumed`)
    assert engine["prefill_batch"] == 2
    assert "serve.prefill_batch" in config["assumed"]
    k = config["builder"]["kwargs"]
    assert config["builder"]["args"] == [config["vocab_size"]] == [65536]
    assert k["param_dtype"] == config["serve"]["compute_dtype"] == "bfloat16"
    assert config["serve"]["matmul_precision"] == "default"
    # the longest context (1,024 + 512) sits on the 2,048 rung
    assert 1024 + 512 <= 2048 <= k["max_len"] \
        == config["max_position_embeddings"] == 4096
    assert k["held"] == [0, config["num_experts"]] == [0, 64]
    assert k["layer_types"] == config["layer_types"] == ["conv"] + [
        "full_attention", "conv", "conv", "conv"] * 2
    assert len(k["layer_types"]) == config["num_hidden_layers"] == 9
    assert k["num_dense_layers"] == config["num_dense_layers"] == 1
    for ours, theirs in (("d_model", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("kv_heads", "num_key_value_heads"),
                         ("conv_L", "conv_L_cache"),
                         ("d_ff", "intermediate_size"),
                         ("d_ff_expert", "moe_intermediate_size"),
                         ("n_experts", "num_experts"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("norm_eps", "norm_eps")):
        assert k[ours] == config[theirs], ours
    assert k["head_dim"] * k["num_heads"] == config["hidden_size"]
    assert k["rope_theta"] == config["rope_parameters"]["rope_theta"] == 1e6
    assert k["router_sum_eps"] == 1e-6
    assert config["routed_scaling_factor"] == 1     # so nothing multiplies it in
    assert config["use_expert_bias"] and config["norm_topk_prob"]
    assert not config["conv_bias"]
    assert {**k, **config["reference"]["kwargs"]} == k
    assert config["reference"]["module"] == "lfm2_moe_ref"
    assert config["serve"]["check"]["control"] == {"lower": "float8_e4m3fn"}
    # the period is the published one: layers 1 and 2-9 of the 40
    pub = config["published"]
    assert pub["layer_types"][1:10] == config["layer_types"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            pub["max_position_embeddings"]) == (40, 2, 128000)


def test_every_width_is_the_catalogs_and_reduced_lists_the_rest(root):
    """Every key of the catalog row's `config` is in the file under the
    same name; the four in `reduced` (depth, `layer_types`,
    `num_dense_layers`, positions) are the only ones that differ, and
    `published` holds those four as the row has them. None is a
    width."""
    row = CATALOG_ROW
    config = _config(root)
    (entry,) = [c for c in cell_mod.benchmark(root)["configs"]
                if c["name"] == "lfm2-24b-a2b"]
    assert entry["source"] == config["source"] == row["source_url"]
    reduced = ["num_hidden_layers", "layer_types", "num_dense_layers",
               "max_position_embeddings"]
    assert entry["reduced"] == config["reduced"] == reduced
    differ = [key for key, value in row["config"].items()
              if config[key] != value]
    assert sorted(differ) == sorted(reduced)
    assert config["published"] == {key: row["config"][key]
                                   for key in reduced}
    assert not any(key.endswith(("_dim", "_rank", "_size")) or "hidden" in
                   key.replace("num_hidden_layers", "") for key in reduced)


def test_the_new_cell_joins_the_lists_the_issue_names(root):
    """At least every list that names both `gpt2-serve-decode` and the
    hybrid model's cell, the routed layer's four, and its own
    `conv_step_ms`; not the window's reader, not `decode_attend`'s
    counter. (That a list names cells in `workloads`' order, so that a
    cell joins at its end, is `check_lists_grow_at_their_end`'s.)"""
    bench = cell_mod.benchmark(root)
    mine = {m["name"] for m in cell_mod.metrics_for(CELL, "per_layer", root)}
    both = {m["name"] for m in bench["per_layer"]
            if {"gpt2-serve-decode", "mimo-v2.5-serve-mixedlen"}
            <= set(m.get("workloads", []))}
    assert mine >= both | {
        "compiles_in_window", "moe_step_ms", "attn_full_step_ms",
        "moe_experts_roofline_pct", "expert_load_max_over_mean",
        "conv_step_ms"}
    assert not {"attn_window_step_ms", "attn_rung_read_pct"} & mine
    assert {m["name"]
            for m in cell_mod.metrics_for(CELL, "end_to_end", root)} \
        == {"out_tokens_per_s", "tpot_p50_ms", "setup_s"}
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "conv_step_ms"]
    listed = entry.pop("workloads")
    assert entry == {"name": "conv_step_ms", "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "model math",
                     "moves": "tpot_p50_ms"}
    # a reader of the device trace lists the cells whose model has the
    # operations: those whose configuration gives the part it sums
    assert CELL in listed
    for name in listed:
        _, config, _ = cell_mod.load_cell(name, root)
        assert "short_conv" in config.get("step_parts", {}), name
    # the step counters the readers need are the model's
    model = cell_mod.resolve_callable(
        _config(root)["builder"]["callable"], root)
    assert model.step_counter_names == (
        "moe_assignments_local", "moe_experts_touched", "moe_expert_load_max")


# -- the control's witness and its planted faults, at toy size -------------------
def test_the_witness_reads_rounding_with_and_without_a_swapped_expert(
        policies):  # noqa: F811
    """`--witness bfloat16` on the float32 toy: the program's eval
    forward chooses what was served and what the float32 reference
    chooses (no routed layer swaps an expert), while the reference
    rounded to bfloat16 swaps some; every reading goes through the
    margin's comparison."""
    out = lfm2_moe_control.run(_toy_run(False, seconds=0.0),
                               lower="bfloat16")
    assert out["served_correct"] and not out["control_correct"]
    assert out["program_eval_correct"] and out["program_eval_worst"] <= 0.001
    assert out["served_equals_program_eval"] == out["judged"] > 0
    swaps = out["judged_where_a_routed_layer_chose_other_experts"]
    assert swaps["program"] == [0] * 8
    assert len(swaps["reference_in_bfloat16"]) == 8
    assert sum(swaps["reference_in_bfloat16"]) > 0
    assert out["judged_with_a_swap"] == 0 == out["served_worst_with_a_swap"]
    assert out["reference_in_bfloat16_worst"] > 0.001
    assert (out["reference_in_bfloat16_routed_as_float32_worst"]
            < out["reference_in_bfloat16_worst"])


@pytest.mark.parametrize("fault", lfm2_moe_control.FAULTS)
def test_a_planted_fault_of_the_state_comes_out_not_correct(
        fault, monkeypatch, policies):  # noqa: F811
    from singa_tpu.models.shortconv_moe import ShortConvMoELM

    for name in ("_prefill_rows", "_slot_step"):    # put back afterwards
        monkeypatch.setattr(ShortConvMoELM, name, getattr(ShortConvMoELM,
                                                          name))
    out = lfm2_moe_control.run(_toy_run(False, seconds=0.0), fault=fault)
    assert out["fault"] == fault
    assert not out["served_correct"] and out["served_worst"] > 0.01
