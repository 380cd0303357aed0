"""What ISSUE 35 adds to the benchmark, on the CPU: the serving driver
drives the chunk-summary model at toy size through to a result line
(the toy cell lives under tests/perfbench/toy_evabyte/, a root of its
own: never a cell, never reachable from the command);
`attn_chunked_step_ms`, `dense_step_ms` and `attn_chunked_roofline_pct`
read a trace given as tuples whose instruction texts are a traced chip
run's; the new cell's files say what ISSUE 35 asked of them; the control
and both planted faults come out not correct. Every test that reads
this repository's files takes its root from the fixture `root`."""
import os
import time

import jax
import pytest

from perfbench import run as run_mod
from perfbench.harness import cell as cell_mod
from perfbench.harness import moe_trace, opcount, opcount_chunked, peaks, profiler
from perfbench.layer_metrics import (attn_chunked_roofline_pct,
                                     attn_chunked_step_ms, dense_step_ms)
from perfbench.reference import evabyte_control
from test_perfbench_contract import config_of
from test_perfbench_drivers import FakeDeviceTrace, _meter, policies  # noqa: F401

TOY = os.path.join(os.path.dirname(__file__), "toy_evabyte")
CELL, CONFIG = "evabyte-serve-longctx32", "evabyte"
# the catalog row `EvaByte` (the model-configs guide's
# architectures.jsonl, read when ISSUE 35 was written): its `source_url`
# and every key of its `config`, written out so that the test reads
# nothing outside the checkout
CATALOG_ROW = {
    "source_url": "https://huggingface.co/EvaByte/EvaByte/blob/main/"
                  "config.json",
    "config": {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096,
        "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
        "intermediate_size": 11008, "lazy_init": True,
        "max_position_embeddings": 32768, "max_seq_length": 32768,
        "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_chunks": None, "num_hidden_layers": 32,
        "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320,
        "window_size": 2048}}
V5E = peaks.for_kind("TPU v5 lite")


def _toy_run(trace, seconds=1.0):
    cell, config, workload = cell_mod.load_cell("toy-evabyte-serve", TOY)
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


def test_a_traced_run_counts_entries_and_reports_no_device_time(
        monkeypatch, policies):  # noqa: F811
    """No device trace file on the CPU: the three readers of device
    time find nothing and are left out (as on a program that lacks the
    parts); the counters and the gauges are the model's own."""
    from singa_tpu import stats

    run, line = _drive(True, monkeypatch)
    assert line["correct"] is True, run.wrong
    m = line["metrics"]
    assert not {"attn_chunked_step_ms", "dense_step_ms",
                "attn_chunked_roofline_pct"} & set(m)
    assert {"tokens_per_step", "decode_step_ms_p50", "prefill_ms_p50",
            "compiles_in_window", "serve_device_idle_pct"} <= set(m)
    d = run.counters["decode"]
    k = run.config["builder"]["kwargs"]
    gauges = stats.cache_stats()["decode"]
    # 3 layers x 4 slots x (k + v) x 4 heads x 12 x 8 positions x 4 B
    assert gauges["cache_bytes_window"] == 3 * 4 * 2 * 4 * 12 * 8 * 4
    lists = gauges["cache_bytes_summary"] // (3 * 4 * 2 * 4 * 12 * 4)
    assert lists * k["chunk"] >= 32 and gauges["cache_bytes_context"] == 0
    assert d["attn_entries_held"] == (3 * 4 * (k["window"] + lists)
                                      * d["decode_steps"])
    assert 0 < d["attn_entries_needed"] < d["attn_entries_held"]
    assert d["chunk_summaries_written"] > 0


def test_the_control_tier_comes_out_not_correct(policies):  # noqa: F811
    """The margin's two sides through the harness's own comparison: the
    served tokens pass it, the greedy choice of the reference computed
    one precision below the configuration's (bfloat16 under the toy's
    float32) does not. The chip's readings at the published widths are
    in PERF.md; this is the same code at toy size."""
    out = evabyte_control.run(_toy_run(False, seconds=0.0))
    assert out["streams"] == 4 and out["lower"] == "bfloat16"
    assert out["served_correct"] and out["served_worst"] <= 0.001
    assert not out["control_correct"] and out["control_worst"] > 0.01


@pytest.mark.parametrize("fault", evabyte_control.FAULTS)
def test_a_planted_fault_of_the_summaries_comes_out_not_correct(
        fault, monkeypatch, policies):  # noqa: F811
    from singa_tpu.models.chunked_attn import ChunkedAttnLM

    for name in ("_seen_summaries", "_slot_step"):   # put back afterwards
        monkeypatch.setattr(ChunkedAttnLM, name, getattr(ChunkedAttnLM, name))
    out = evabyte_control.run(_toy_run(False, seconds=0.0), fault=fault)
    assert out["fault"] == fault
    assert not out["served_correct"] and out["served_worst"] > 0.01


# -- the device-time readers, on tuples ------------------------------------------
# instruction texts of `evabyte-serve-longctx32`'s traced run on the chip
# (PR 35, seed 2147485001), layouts cut: a layer's scores over its key
# buffer and its weighted sum over its summary values, the buffer write
# (the Pallas call carries the scope's name) and its operand's
# re-layout, the joint softmax, the pooling kernel (by its own name:
# its operands are the attention's), the fused q/k/v projection, the
# MLP's gate product, its down product, and what belongs to no part:
# the head, here with the token program's argmax
SCORES = ("%multiply_reduce_fusion.79 = f32[32,32,2048] fusion("
          "bf16[32,32,128,2048] %attn_chunked.86, f32[32,32,128] "
          "%maximum_convert_fusion.13), kind=kLoop")
REMOTE = ("%multiply_reduce_fusion.75 = f32[32,32,128] fusion("
          "bf16[32,32,128,1024] %get-tuple-element.2103, f32[32,32,1024] "
          "%slice_convert_fusion.25), kind=kLoop")
WRITE = ('%attn_chunked.85 = bf16[32,32,128,2048] custom-call(s32[32] '
         '%get-tuple-element.1908, bf16[32,32,128,2048] '
         '%get-tuple-element.2104, bf16[32,32,128,1] %squeeze_reshape.48), '
         'custom_call_target="tpu_custom_call"')
COLUMN = ("%squeeze_reshape.48 = bf16[32,32,128,1] reshape(bf16[32,1,4096] "
          "%get-tuple-element.1918)")
SOFTMAX = ("%fusion.404 = (f32[32,32], f32[32,32,3072]) fusion("
           "f32[32,32,1024] %multiply_reduce_fusion.78, f32[32,32,2048] "
           "%multiply_reduce_fusion.79, pred[32,3072] %copy-done.1), "
           "kind=kOutput")
POOL = ('%chunk_summary.47 = (bf16[32,32,128,1024], bf16[32,32,128,1024]) '
        'custom-call(s32[32] %copy-done.52, s32[32] %get-tuple-element.1916, '
        's32[32] %get-tuple-element.1917, bf16[32,32,128,2048] '
        '%attn_chunked.94, bf16[32,32,128,2048] %attn_chunked.95, '
        'f32[32,128,1] %bitcast.9), custom_call_target="tpu_custom_call"')
QKV = ("%convolution_bitcast_fusion.12 = bf16[32,1,12288] fusion("
       "bf16[4096,12288] %get-tuple-element.2222, bf16[32,4096] %fusion.398, "
       "f32[4096] %broadcast_add_fusion.28, f32[32] %add_rsqrt_fusion.26), "
       "kind=kOutput")
GATE = ("%fusion.402 = bf16[32,11008] fusion(bf16[4096,11008] "
        "%get-tuple-element.2227, bf16[32,4096] %get-tuple-element.1926, "
        "f32[4096] %copy-done.34, f32[32] %add_rsqrt_fusion.27), kind=kOutput")
DOWN = ("%multiply_reduce_fusion.77 = (f32[32], f32[32,4096]) fusion("
        "bf16[11008,4096] %get-tuple-element.2229, bf16[4096,11008] "
        "%get-tuple-element.2228, bf16[32,11008] %fusion.402), kind=kOutput")
HEAD = ("%iota_reduce_fusion.2 = (bf16[32], s32[32]) fusion(bf16[4096,2560] "
        "%get-tuple-element.2276, f32[32,4096] %get-tuple-element.2001, "
        "bf16[32,4096] %get-tuple-element.2003, f32[4096] "
        "%broadcast_add_fusion.40, f32[32] %add_rsqrt_fusion.38), "
        "kind=kOutput")


def _traced(config, steps, seconds):
    run = cell_mod.Run(cell={"name": CELL}, config=config, workload={},
                       seconds=30.0, trace=True, peaks=V5E)
    run.moe_trace = {"steps": steps, "step_seconds": 1.1 * sum(
        seconds.values()), "seconds": seconds}
    return run


def test_the_three_parts_are_read_from_a_trace_by_the_files_own_patterns(
        root):
    mods = [("jit_slot_scan_1(123)", 0, 1000),
            ("jit_prefill_rows(5)", 1000, 3000),
            ("jit_slot_scan_8(77)", 3000, 11000)]
    ops = [(QKV, 0, 20), (COLUMN, 20, 30), (WRITE, 30, 40),
           (SCORES, 40, 140), (REMOTE, 140, 190), (SOFTMAX, 190, 200),
           (POOL, 200, 250), (GATE, 250, 280), (DOWN, 280, 320),
           (HEAD, 320, 330),
           (QKV, 1500, 2500),                  # a prefill's: not a step's
           (SCORES, 3000, 3800), (POOL, 3800, 4200), (GATE, 4200, 4400)]
    config = config_of(root, CONFIG)
    rx = moe_trace.part_patterns(config["step_parts"], 32)
    assert list(rx) == ["chunk_summary", "attn_chunked", "dense"]
    red = moe_trace.reduce(ops, mods, rx, 0, 20000)
    assert red["steps"] == 9
    assert red["seconds"]["chunk_summary"] == pytest.approx(450e-9)
    assert red["seconds"]["attn_chunked"] == pytest.approx(980e-9)
    assert red["seconds"]["dense"] == pytest.approx(290e-9)
    run = _traced(config, 9, red["seconds"])
    assert attn_chunked_step_ms.read(run) == pytest.approx(1e3 * 1430e-9 / 9)
    assert dense_step_ms.read(run) == pytest.approx(1e3 * 290e-9 / 9)
    for reader in (attn_chunked_step_ms, dense_step_ms):
        assert (reader.LAYER, reader.UNIT, reader.MOVES) \
            == ("model math", "ms", "out_tokens_per_s")
    # a configuration without the parts (the lfm2 model's), or a run
    # without a trace, leaves the readers with nothing: the metrics are
    # left out, as on the parent of the PR that added them
    lfm2 = config_of(root, "lfm2-24b-a2b")
    other = cell_mod.Run(cell={"name": "x"}, config=lfm2, workload={},
                         seconds=30.0, trace=True, peaks=V5E)
    other.moe_trace = moe_trace.reduce(
        ops, mods, moe_trace.part_patterns(lfm2["step_parts"], 32), 0, 20000)
    bare = cell_mod.Run(cell={"name": CELL}, config=config, workload={},
                        seconds=30.0, trace=False, peaks=V5E)
    for reader in (attn_chunked_step_ms, dense_step_ms,
                   attn_chunked_roofline_pct):
        assert reader.read(other) is None and reader.read(bare) is None


def test_the_roofline_share_is_what_the_rows_needed_over_what_it_took(root):
    """32 rows x 6 layers hold 3,072 entries each and need 1,152 of
    them (37.5 %); a program that reads everything held at 80 % of the
    HBM's peak reads 30 %: the share can pass 100 only where a program
    reads less than the rows need."""
    config = config_of(root, CONFIG)
    spec = config["opcount"]
    assert spec["module"] == "opcount_chunked"
    entry = 2 * 32 * 128 * 2
    ops, nbytes = getattr(opcount_chunked, spec["function"])(
        1, **spec["kwargs"])
    assert nbytes == entry == config["bytes"]["entry"] == 16384
    assert opcount.roofline_seconds(ops, nbytes, V5E)[1] == "memory"
    steps, held, needed = 100, 32 * 6 * 3072, 32 * 6 * 1152
    took = steps * held * entry / (0.8 * V5E["hbm_bytes_per_s"])
    run = _traced(config, steps, {"attn_chunked": 0.9 * took,
                                  "chunk_summary": 0.1 * took, "dense": 0.3})
    run.counters["decode"] = {"decode_steps": 1000,
                              "attn_entries_needed": 1000 * needed,
                              "attn_entries_held": 1000 * held,
                              "chunk_summaries_written": 1000 * 12}
    assert attn_chunked_roofline_pct.read(run) == pytest.approx(
        80.0 * needed / held)
    assert "memory-bound" in run.notes["attn_chunked_roofline"]
    assert "37.5 %" in run.notes["attn_chunked_roofline"]
    assert (attn_chunked_roofline_pct.LAYER, attn_chunked_roofline_pct.UNIT,
            attn_chunked_roofline_pct.MOVES) == ("kernels", "%",
                                                 "out_tokens_per_s")
    # a program that counts no entries (another model's) reads nothing
    run.counters["decode"] = {"decode_steps": 1000}
    assert attn_chunked_roofline_pct.read(run) is None


@pytest.mark.parametrize("name", [CONFIG, "toy_evabyte"])
def test_the_files_parts_are_its_own_widths(root, name):
    """`step_parts` is written out from a traced run's instruction
    texts, not derived, so hold it to the widths the same file gives
    the model: the buffers [slots, H, D, W] and the lists [slots, H, D,
    rung / C] on the cell's rung, the joint scores [slots, H, W + rung /
    C], the written column; the fused q/k/v matrix, the MLP's three and
    the output projection; the pooling kernel by its name, first."""
    config = config_of(TOY if name == "toy_evabyte" else root, name)
    k = config["builder"]["kwargs"]
    H, D, W, d, f = (k["num_heads"], k["head_dim"], k["window"],
                     k["d_model"], k["d_ff"])
    rung = {CONFIG: 16384, "toy_evabyte": 32}[name]
    R = rung // k["chunk"]
    assert config["step_parts"] == {
        "chunk_summary": [r"^%chunk_summary[.\d]* = "],
        "attn_chunked": [rf"\[{{slots}},{H},{D},{W}\]",
                         rf"\[{{slots}},{H},{D},{R}\]",
                         rf"\[{{slots}},{H},{W + R}\]",
                         rf"\[{{slots}},{H},{D},1\]"],
        "dense": [rf"\[{d},{3 * H * D}\]", rf"\[{d},{f}\]", rf"\[{f},{d}\]",
                  rf"\[{d},{H * D}\]"]}
    assert list(config["step_parts"]) == ["chunk_summary", "attn_chunked",
                                          "dense"]
    assert config["opcount"]["kwargs"] == {
        "num_heads": H, "head_dim": D,
        "itemsize": 2 if k["param_dtype"] == "bfloat16" else 4}


# -- the new cell's files say what was asked -------------------------------------
def test_the_serving_cell_is_the_issues(root):
    cell, config, w = cell_mod.load_cell(CELL, root)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "serve-longctx32", 1)
    assert len(cell["why"]) <= 200
    assert "6 of 32" in cell["why"] and "16384" in cell["why"]
    assert cell in cell_mod.benchmark(root)["workloads"]
    assert (w["loop"], w["clients"]) == ("closed", 32)
    assert w["prompt_len"] == {"dist": "lognormal", "median": 4096,
                               "sigma": 0.6, "min": 2048, "max": 12288}
    assert w["output_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 0.5, "min": 256, "max": 2048}
    assert w["first_output_scale"] == "uniform" and w["grace_s"] == 0
    assert w["trace_seconds"] == 4 and "engine" not in w
    engine = config["serve"]["engine"]
    assert engine == {"max_sessions": 32, "max_new_tokens": 2048,
                      "prefill_batch": 1}
    assert engine["max_sessions"] == w["clients"]
    k = config["builder"]["kwargs"]
    assert config["builder"]["args"] == [config["vocab_size"]] == [320]
    assert k["param_dtype"] == config["serve"]["compute_dtype"] == "bfloat16"
    assert config["serve"]["matmul_precision"] == "default"
    # the longest context (12,288 + 2,048) sits on the 16,384 rung, and
    # every prompt has crossed a block boundary before its first byte
    assert 12288 + 2048 <= 16384 == k["max_len"] \
        == config["max_position_embeddings"] == config["max_seq_length"]
    assert w["prompt_len"]["min"] >= k["window"] == config["window_size"]
    for ours, theirs in (("d_model", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_heads", "num_key_value_heads"),
                         ("window", "window_size"), ("chunk", "chunk_size"),
                         ("rope_theta", "rope_theta"),
                         ("num_layers", "num_hidden_layers"),
                         ("d_ff", "intermediate_size"),
                         ("pred_heads", "num_pred_heads"),
                         ("norm_eps", "rms_norm_eps"),
                         ("init_std", "init_std")):
        assert k[ours] == config[theirs], ours
    assert k["head_dim"] * k["num_heads"] == config["hidden_size"]
    assert k["num_layers"] == 6 and k["window"] % k["chunk"] == 0
    ref_kw = config["reference"]["kwargs"]
    assert {**k, **{n: v for n, v in ref_kw.items() if n != "vocab_size"}} \
        == k and ref_kw["vocab_size"] == 320
    assert config["reference"]["module"] == "evabyte_ref"
    assert config["serve"]["check"]["control"] == {"lower": "float8_e4m3fn"}
    assert config["published"] == {"num_hidden_layers": 32,
                                   "max_position_embeddings": 32768,
                                   "max_seq_length": 32768}
    for word in ("pool_logits", "pool_offset", "phi_mu_draw",
                 "next_byte_head", "rope_pairing", "norms", "initializer",
                 "serve.prefill_batch"):
        assert word in config["assumed"], word
    assert "pipeline" in config["deployment"]
    # the bytes the file states are its own widths'
    by = config["bytes"]
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert by["parameters_a_layer"] == layer == 202375168
    assert by["cache_bytes_window"] == 6 * 32 * 2 * 32 * 128 * 2048 * 2
    assert by["cache_bytes_summary"] == 6 * 32 * 2 * 32 * 128 * 1024 * 2
    assert by["weights_and_slab"] == (by["weights"] + by["cache_bytes_window"]
                                      + by["cache_bytes_summary"])
    assert 2.45e9 < by["weights"] < 2.46e9
    assert by["cache_bytes_window"] + by["cache_bytes_summary"] > by["weights"]


def test_every_width_is_the_catalogs_and_reduced_lists_the_rest(root):
    """Every key of the catalog row's `config` is in the file under the
    same name; the three in `reduced` (depth and the two position
    limits) are the only ones that differ, and `published` holds those
    as the row has them. None is a width."""
    row = CATALOG_ROW
    config = config_of(root, CONFIG)
    (entry,) = [c for c in cell_mod.benchmark(root)["configs"]
                if c["name"] == CONFIG]
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    reduced = ["num_hidden_layers", "max_position_embeddings",
               "max_seq_length"]
    assert entry["reduced"] == config["reduced"] == reduced
    differ = [key for key, value in row["config"].items()
              if config[key] != value]
    assert sorted(differ) == sorted(reduced)
    assert config["published"] == {key: row["config"][key]
                                   for key in reduced}
    assert not any(key.endswith(("_dim", "_rank", "_size")) or "hidden" in
                   key.replace("num_hidden_layers", "") for key in reduced)


def test_the_new_cell_joins_the_lists_its_metrics_allow(root):
    """The cell reports `out_tokens_per_s` and `setup_s` end to end and
    NOT `tpot_p50_ms`: over two sets of six runs on the chip its median
    over the 29-37 requests a 30 s window finishes spread 13.6 / 8.5 %
    against half its bound, 5 (PERF.md section 6, PR 35), and a metric
    that cannot be admitted is not registered. So of the per-layer
    lists it joins those that move what it reports: every list that
    names both `gpt2-serve-decode` and the hybrid model's cell and
    moves `out_tokens_per_s`, and its own three; not the routed
    layer's, not another model's attention's, not `decode_attend`'s
    counter. (That a list names cells in `workloads`' order, so that a
    cell joins at its end, is `check_lists_grow_at_their_end`'s.)"""
    bench = cell_mod.benchmark(root)
    mine = {m["name"] for m in cell_mod.metrics_for(CELL, "per_layer", root)}
    both = {m["name"] for m in bench["per_layer"]
            if {"gpt2-serve-decode", "mimo-v2.5-serve-mixedlen"}
            <= set(m.get("workloads", []))
            and m["moves"] == "out_tokens_per_s"}
    own = {"attn_chunked_step_ms", "attn_chunked_roofline_pct",
           "dense_step_ms"}
    assert both >= {"tokens_per_step", "serve_device_idle_pct",
                    "serve_peak_hbm_gb", "steps_tokens_only_pct"}
    assert mine == both | own | {"compiles_in_window"}
    reported = {m["name"]
                for m in cell_mod.metrics_for(CELL, "end_to_end", root)}
    assert reported == {"out_tokens_per_s", "setup_s"}
    moved = {m["moves"] for m in bench["per_layer"] if m["name"] in mine}
    assert moved <= reported
    by_name = {m["name"]: dict(m) for m in bench["per_layer"]}
    for name, unit, better, layer, moves in (
            ("attn_chunked_step_ms", "ms", "lower", "model math",
             "out_tokens_per_s"),
            ("attn_chunked_roofline_pct", "%", "higher", "kernels",
             "out_tokens_per_s"),
            ("dense_step_ms", "ms", "lower", "model math",
             "out_tokens_per_s")):
        entry = by_name[name]
        listed = entry.pop("workloads")
        assert entry == {"name": name, "unit": unit, "better": better,
                         "source": "device_trace", "layer": layer,
                         "moves": moves}
        # a reader of the device trace lists the cells whose model has
        # the operations: those whose configuration gives the parts
        assert CELL in listed
        for cell_name in listed:
            _, config, _ = cell_mod.load_cell(cell_name, root)
            assert {"attn_chunked", "chunk_summary", "dense"} \
                <= set(config.get("step_parts", {})), cell_name
    # the step counters the roofline's reader needs are the model's
    model = cell_mod.resolve_callable(
        config_of(root, CONFIG)["builder"]["callable"], root)
    assert set(attn_chunked_roofline_pct.COUNTERS) \
        <= set(model.step_counter_names)
