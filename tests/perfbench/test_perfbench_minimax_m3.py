"""What ISSUE 39 adds to the benchmark, on the CPU: the serving driver
drives the learned block-sparse model at toy size through to a result
line (the toy cell lives under tests/perfbench/toy_minimax_m3/, a root
of its own: never a cell, never reachable from the command); the
addition is data the harness reads by name, with no edit to a file it
had; `attn_sparse_step_ms`, `attn_sparse_roofline_pct` and
`attn_sparse_read_pct` read their parts and counters; the new cell's
files say what ISSUE 39 asked of them; the control and the three
planted faults come out not correct. Every test that reads this
repository's files takes its root from the fixture `root`."""
import os
import time

import jax
import pytest

from perfbench import run as run_mod
from perfbench.harness import cell as cell_mod
from perfbench.harness import moe_trace, opcount, opcount_sparse, peaks
from perfbench.harness import profiler, traffic
from perfbench.layer_metrics import (attn_sparse_read_pct,
                                     attn_sparse_roofline_pct,
                                     attn_sparse_step_ms,
                                     blocksparse_dense_step_ms,
                                     moe_experts_roofline_pct,
                                     moe_experts_step_ms)
from perfbench.reference import minimax_m3_control
from test_perfbench_contract import config_of
from test_perfbench_drivers import FakeDeviceTrace, _meter, policies  # noqa: F401

TOY = os.path.join(os.path.dirname(__file__), "toy_minimax_m3")
CELL, CONFIG = "minimax-m3-serve-longctx20", "minimax-m3"
NEW_METRICS = ("attn_sparse_step_ms", "attn_sparse_roofline_pct",
               "attn_sparse_read_pct", "moe_experts_step_ms",
               "blocksparse_dense_step_ms")
# the catalog row `MiniMax-M3` (the model-configs guide's
# architectures.jsonl, read when ISSUE 39 was written): its `source_url`
# and every key of its `config`, written out so that the test reads
# nothing outside the checkout
CATALOG_ROW = {
    "source_url": "https://huggingface.co/MiniMaxAI/MiniMax-M3/blob/main/"
                  "config.json",
    "config": {
        "hidden_size": 6144, "intermediate_size": 3072,
        "num_hidden_layers": 60, "num_attention_heads": 64,
        "num_key_value_heads": 4, "head_dim": 128, "vocab_size": 200064,
        "max_position_embeddings": 1048576, "rms_norm_eps": 1e-06,
        "use_gemma_norm": True, "attention_output_gate": False,
        "rope_theta": 5000000, "rotary_dim": 64,
        "partial_rotary_factor": 0.5, "hidden_act": "swigluoai",
        "use_qk_norm": True, "tie_word_embeddings": False,
        "dense_intermediate_size": 12288, "shared_intermediate_size": 3072,
        "num_local_experts": 128, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "scoring_func": "sigmoid",
        "use_routing_bias": True, "moe_layer_freq": [0, 0, 0] + [1] * 57,
        "qk_norm_type": "per_head", "num_mtp_modules": 7,
        "num_nextn_predict_layers": 1, "swiglu_alpha": 1.702,
        "swiglu_limit": 7, "routed_scaling_factor": 2}}
V5E = peaks.for_kind("TPU v5 lite")


def _toy_run(trace, seconds=1.0):
    cell, config, workload = cell_mod.load_cell("toy-minimax-m3-serve", TOY)
    return cell_mod.Run(cell=cell, config=config, workload=workload,
                        seconds=seconds, trace=trace, seed=2147483939,
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
    assert set(line["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "margin 0.001" in run.notes["reference_check"]
    assert line["compared"]["served_token_under_reference_best"][
        "value"] <= 0.001


def test_a_traced_run_counts_what_the_selection_read(monkeypatch,
                                                     policies):  # noqa: F811
    """No device trace file on the CPU: the readers of device time find
    nothing and are left out (as on a program that lacks the parts);
    the counters, the read share and the gauges are the model's own."""
    from singa_tpu import stats

    run, line = _drive(True, monkeypatch)
    assert line["correct"] is True, run.wrong
    m = line["metrics"]
    assert not {"attn_sparse_step_ms", "attn_sparse_roofline_pct",
                "moe_experts_roofline_pct"} & set(m)
    assert {"tokens_per_step", "attn_sparse_read_pct", "compiles_in_window",
            "serve_device_idle_pct"} <= set(m)
    d = run.counters["decode"]
    k = run.config["builder"]["kwargs"]
    gauges = stats.cache_stats()["decode"]
    # 3 layers x 4 slots x (k + v) x 2 groups x 16 x 256 positions x 4 B,
    # and a pooled key of 16 a block of 8
    assert gauges["cache_bytes_context"] == 3 * 4 * 2 * 2 * 16 * 256 * 4
    assert gauges["cache_bytes_blockkey"] == 3 * 4 * 2 * 16 * 32 * 4
    assert d["msa_positions_read"] == k["block"] * d["msa_blocks_selected"]
    assert 0 < d["msa_positions_read"] < d["msa_positions_held"]
    assert m["attn_sparse_read_pct"]["value"] == pytest.approx(
        100.0 * d["msa_positions_read"] / d["msa_positions_held"])
    assert m["attn_sparse_read_pct"]["value"] < 100.0


def test_the_control_tier_comes_out_not_correct(policies):  # noqa: F811
    """The margin's two sides through the harness's own comparison: the
    served tokens pass it, the greedy choice of the reference computed
    one precision below the configuration's (bfloat16 under the toy's
    float32) does not; the program picks what the reference picks."""
    out = minimax_m3_control.run(_toy_run(False, seconds=0.0))
    assert out["streams"] == 4 and out["lower"] == "bfloat16"
    assert out["served_correct"] and out["served_worst"] <= 0.001
    assert not out["control_correct"] and out["control_worst"] > 0.01
    # both paths of the program's selection: the prefill's, and the
    # decode step's from the slab's pooled keys along each reply
    assert out["selection_disagreement"] == 0.0
    assert out["selection_disagreement_prefill_by_layer"] == [0.0] * 3
    assert out["selection_disagreement_decode"] == 0.0
    assert out["selection_disagreement_decode_by_layer"] == [0.0] * 3


def test_the_witness_reads_the_selection_of_a_cut_program(
        monkeypatch, policies):  # noqa: F811
    """`--witness`: the program cut to its first layers and drawn in
    the named dtype picks what the reference picks on both paths at
    float32; through a decode step that never updates its pooled keys
    the decode path's reading moves and the prefill's does not."""
    from singa_tpu.models.block_sparse_moe import BlockSparseMoELM

    out = minimax_m3_control.witness(_toy_run(False, seconds=0.0),
                                     "float32", 2)
    assert (out["witness"], out["layers"], out["streams"]) == (
        "float32", 2, 4)
    assert out["selection_disagreement_prefill_by_layer"] == [0.0] * 2
    assert out["selection_disagreement_decode_by_layer"] == [0.0] * 2
    monkeypatch.setattr(BlockSparseMoELM, "_slot_step",
                        BlockSparseMoELM.__dict__["_slot_step"])
    minimax_m3_control.plant("blockkey_stale")
    out = minimax_m3_control.witness(_toy_run(False, seconds=0.0),
                                     "float32", 1)
    assert out["selection_disagreement_prefill_by_layer"] == [0.0]
    assert out["selection_disagreement_decode_by_layer"][0] > 0.05


@pytest.mark.parametrize("fault", minimax_m3_control.FAULTS)
def test_a_planted_fault_of_the_selection_comes_out_not_correct(
        fault, monkeypatch, policies):  # noqa: F811
    from singa_tpu.models.block_sparse_moe import BlockSparseMoELM

    for name in ("_selection", "_slot_step", "__init__"):
        monkeypatch.setattr(BlockSparseMoELM, name,
                            BlockSparseMoELM.__dict__[name])
    out = minimax_m3_control.run(_toy_run(False, seconds=0.0), fault=fault)
    assert out["fault"] == fault
    assert not out["served_correct"] and out["served_worst"] > 0.01


# -- the addition is data ---------------------------------------------------------
def test_the_addition_is_new_files_the_harness_reads_by_name(root):
    """The cell, its configuration, its traffic and its three metrics
    resolve by name through the harness as it was: no file of the
    harness, the drivers or the command names the model, the cell or a
    new metric; the generator reads the traffic file's keys; each new
    metric's reader states what its entry states."""
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for sub in ("perfbench/harness", "perfbench/drivers", "perfbench/run.py"):
        path = os.path.join(here, sub)
        files = ([os.path.join(path, f) for f in os.listdir(path)
                  if f.endswith(".py") and f != "opcount_sparse.py"]
                 if os.path.isdir(path) else [path])
        for name in files:
            text = open(name).read()
            for word in ("minimax", "block_sparse", "msa_", *NEW_METRICS):
                assert word not in text, (name, word)
    cell, config, w = cell_mod.load_cell(CELL, root)
    assert traffic.prompt_buckets(w) == [4096, 8192, 16384, 24576]
    assert traffic.limits(w) == (4096, 24576, 4096)
    by_name = {m["name"]: m for m in cell_mod.benchmark(root)["per_layer"]}
    for name in NEW_METRICS:
        reader = cell_mod.module("layer_metrics", name)
        entry = by_name[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            entry["layer"], entry["unit"], entry["moves"])
        assert entry["workloads"] == [CELL]
    model = cell_mod.resolve_callable(config["builder"]["callable"], root)
    for reader in (attn_sparse_read_pct, attn_sparse_roofline_pct):
        assert set(reader.COUNTERS) <= set(model.step_counter_names)


# -- the device-time readers, on tuples ------------------------------------------
# instruction texts of the decode step as the compiler writes it for a
# v5e at the cell's geometry (tests/test_tpu_compile_widths.py), with
# their operands' types as a trace's event names carry them: the kernel,
# the key's write and its column's re-layout, the kernel's result cast,
# the indexer's scores over the pooled keys, its sort, the pooled keys'
# running max, a held expert's gate product and down product, the fused
# q/k/v projection, the shared expert's down product beside the routed
# part's sum, and what belongs to no part: the head
KERNEL = ("%selected_blocks_attend.5 = f32[20,4,16,128] custom-call(s32[1520] "
          "%reshape.0, s32[80] %reshape.1, s32[20] %po.1, bf16[20,4,16,128] "
          "%bitcast.476, bf16[20,4,128,32768] %cache_write.10), "
          'custom_call_target="tpu_custom_call"')
WRITE = ("%cache_write.10 = bf16[20,4,128,32768] custom-call(s32[20] %po.1, "
         "bf16[20,4,128,32768] %c_0___k__.1, bf16[20,4,128,1] %copy.352), "
         'custom_call_target="tpu_custom_call"')
COLUMN = ("%copy.352 = bf16[20,4,128,1] copy(bf16[20,4,128,1] "
          "%maximum_bitcast_fusion)")
CAST = ("%convert_element_type.458 = bf16[20,4,16,128] convert(f32[20,4,16,"
        "128] %selected_blocks_attend.5)")
SCORES = ("%fusion.131 = f32[20,4,256] fusion(bf16[20,4,128,256] "
          "%select_select_fusion.4, bf16[20,4,4,128] %reshape.56, f32[20,4,4] "
          "%copy.344), kind=kOutput")
SORT = ("%sort = (f32[20,4,256], s32[20,4,256]) sort(f32[20,4,256] "
        "%fusion.190, s32[20,4,256] %iota), dimensions={2}, is_stable=true")
POOL = ("%select_select_fusion.4 = bf16[20,4,128,256] fusion(bf16[20,4,128,"
        "256] %custom-call.28, pred[20] %fusion.259, pred[256] "
        "%iota_compare_fusion.14, bf16[20,4,128] %broadcast_in_dim.4), "
        "kind=kLoop")
GATE = ("%fusion.157 = bf16[8,3072,20] fusion(bf16[8,6144,3072] "
        "%p__blocks___1___ffn____W_g__.1, bf16[20,6144] "
        "%multiply_convert_fusion.13), kind=kOutput")
DOWN = ("%fusion.44 = bf16[20,6144,1] fusion(bf16[8,3072,6144] "
        "%p__blocks___1___ffn____W_d__.1, bf16[8,3072,20] %fusion.159, "
        "bf16[8,3072,20] %fusion.157, f32[20,8] %bitcast.485), kind=kOutput")
QKV = ("%convolution_bitcast_fusion.4 = bf16[20,1,9216] fusion(bf16[6144,"
       "9216] %copy-done, bf16[20,6144] %copy-done.7, f32[6144] "
       "%broadcast_add_fusion.10, f32[20] %add_rsqrt_fusion.20), "
       "kind=kOutput")
SHARED = ("%multiply_reduce_fusion.10 = (f32[20], f32[20,6144]) fusion("
          "f32[20,6144] %copy-done.12, bf16[3072,6144] "
          "%p__blocks___1___shared____W_d__.1, bf16[20,3072] %copy-done.20, "
          "bf16[20,3072] %fusion.326, bf16[20,6144,1] %fusion.44), "
          "kind=kOutput")
HEAD = ("%fusion.9 = f32[20,25008] fusion(bf16[6144,25008] %p__head__.1, "
        "bf16[20,6144] %fusion.8), kind=kOutput")


def _traced(config, steps, seconds):
    run = cell_mod.Run(cell={"name": CELL}, config=config, workload={},
                       seconds=30.0, trace=True, peaks=V5E)
    run.moe_trace = {"steps": steps, "step_seconds": 1.1 * sum(
        seconds.values()), "seconds": seconds}
    return run


def test_the_four_parts_are_read_from_a_trace_by_the_files_own_patterns(
        root):
    mods = [("jit_slot_scan_1(123)", 0, 1000),
            ("jit_prefill_rows(5)", 1000, 3000),
            ("jit_slot_scan_8(77)", 3000, 11000)]
    ops = [(QKV, 0, 20), (POOL, 20, 30), (SCORES, 30, 40), (SORT, 40, 50),
           (COLUMN, 50, 55), (WRITE, 55, 65), (KERNEL, 65, 165),
           (CAST, 165, 170), (GATE, 170, 270), (DOWN, 270, 370),
           (SHARED, 370, 400), (HEAD, 400, 450),
           (QKV, 1500, 2500),                  # a prefill's: not a step's
           (KERNEL, 3000, 3800), (POOL, 3800, 4200), (GATE, 4200, 4400)]
    config = config_of(root, CONFIG)
    # the harness fills `{slots}` with the next power of two (32); the
    # file writes the pool's 20 out
    rx = moe_trace.part_patterns(config["step_parts"], 32)
    assert list(rx) == ["attn_sparse", "msa_indexer", "moe_experts", "dense"]
    red = moe_trace.reduce(ops, mods, rx, 0, 20000)
    assert red["steps"] == 9
    assert red["seconds"]["attn_sparse"] == pytest.approx(920e-9)
    assert red["seconds"]["msa_indexer"] == pytest.approx(430e-9)
    assert red["seconds"]["moe_experts"] == pytest.approx(400e-9)
    assert red["seconds"]["dense"] == pytest.approx(50e-9)
    run = _traced(config, 9, red["seconds"])
    assert attn_sparse_step_ms.read(run) == pytest.approx(1e3 * 1350e-9 / 9)
    # the two largest parts have their readers too: the experts' and
    # the dense products'
    assert moe_experts_step_ms.read(run) == pytest.approx(1e3 * 400e-9 / 9)
    assert blocksparse_dense_step_ms.read(run) == pytest.approx(
        1e3 * 50e-9 / 9)
    # a configuration without the parts (the lfm2 model's), or a run
    # without a trace, leaves the readers with nothing: the metrics are
    # left out, as on the parent of the PR that added them
    lfm2 = config_of(root, "lfm2-24b-a2b")
    other = cell_mod.Run(cell={"name": "x"}, config=lfm2, workload={},
                         seconds=30.0, trace=True, peaks=V5E)
    other.moe_trace = moe_trace.reduce(
        ops, mods, moe_trace.part_patterns(lfm2["step_parts"], 16), 0, 20000)
    bare = cell_mod.Run(cell={"name": CELL}, config=config, workload={},
                        seconds=30.0, trace=False, peaks=V5E)
    for reader in (attn_sparse_step_ms, attn_sparse_roofline_pct):
        assert reader.read(other) is None and reader.read(bare) is None
    for reader in (moe_experts_step_ms, blocksparse_dense_step_ms):
        assert reader.read(bare) is None


def test_the_roofline_share_is_what_the_selection_needed_over_what_it_took(
        root):
    """20 rows x 5 layers x 4 groups at 10,240 held positions each, 19
    blocks of 128 read: the least time moves 2,432 positions' keys and
    values in bfloat16 and 80 float32 pooled keys a (row, layer, group); a program that
    moved every held position at 80 % of the HBM's peak reads under a
    quarter of that; the read share is read over held."""
    config = config_of(root, CONFIG)
    spec = config["opcount_sparse"]
    assert spec["module"] == "opcount_sparse"
    ops, nbytes = getattr(opcount_sparse, spec["function"])(
        1, 0, **spec["kwargs"])
    assert nbytes == 2 * 128 * 2 == config["bytes"][
        "slot_position_all_layers"] // 5 // 4
    assert opcount.roofline_seconds(ops, nbytes, V5E)[1] == "memory"
    rows = 20 * 5 * 4
    held, read = rows * 10240, rows * 19 * 128
    steps = 100
    took = steps * held * 2 * 128 * 2 / (0.8 * V5E["hbm_bytes_per_s"])
    run = _traced(config, steps, {"attn_sparse": 0.9 * took,
                                  "msa_indexer": 0.1 * took, "dense": 0.3})
    run.counters["decode"] = {"decode_steps": 1000,
                              "msa_positions_read": 1000 * read,
                              "msa_positions_held": 1000 * held,
                              "msa_blocks_selected": 1000 * rows * 19}
    need = read * 512 + held / 128 * 512
    assert attn_sparse_roofline_pct.read(run) == pytest.approx(
        80.0 * need / (held * 512))
    assert "memory-bound" in run.notes["attn_sparse_roofline"]
    assert "23.8 %" in run.notes["attn_sparse_roofline"]
    assert attn_sparse_read_pct.read(run) == pytest.approx(
        100.0 * 19 * 128 / 10240)
    # a program that counts no selection (another model's) reads nothing
    run.counters["decode"] = {"decode_steps": 1000}
    assert attn_sparse_roofline_pct.read(run) is None
    assert attn_sparse_read_pct.read(run) is None
    # the routed layer's reader takes the file's `opcount`, as it does
    # the other two routed models'
    assert config["opcount"] == {
        "module": "opcount_moe", "function": "expert_products",
        "kwargs": {"d_model": 6144, "d_ff_expert": 3072, "itemsize": 2}}
    assert moe_experts_roofline_pct.read(run) is None


@pytest.mark.parametrize("name", [CONFIG, "toy_minimax_m3"])
def test_the_files_parts_are_its_own_widths(root, name):
    """`step_parts` is written out, not derived, so hold it to the
    widths the same file gives the model: the kernel and the write by
    name, the written column [slots, G, D, 1] and [slots, G, 1, D] and
    the kernel's result [slots, G, Hg, D]; the pooled keys [slots, G,
    Di, rung / N], the scores [slots, G, rung / N], the top [slots, G,
    top] and qI [slots, G, J, Di]; the held experts' matrices; every
    projection and the dense and shared MLPs'."""
    config = config_of(TOY if name == "toy_minimax_m3" else root, name)
    k = config["builder"]["kwargs"]
    H, G, D, d = k["num_heads"], k["kv_heads"], k["head_dim"], k["d_model"]
    J, Di, E = k["index_heads"], k["index_dim"], k["held"][1]
    f, fd, fs = k["d_ff_expert"], k["d_ff"], k["d_ff_shared"]
    nb = {CONFIG: 32768, "toy_minimax_m3": 256}[name] // k["block"]
    # the toy's pool is a power of two; the cell's 20 is written out
    slots = {CONFIG: 20, "toy_minimax_m3": "{slots}"}[name]
    assert config["step_parts"] == {
        "attn_sparse": [r"^%selected_blocks_attend[.\d]* = ",
                        r"^%cache_write[.\d]* = ",
                        rf"\[{slots},{G},{D},1\]",
                        rf"\[{slots},{G},1,{D}\]",
                        rf"\[{slots},{G},{H // G},{D}\]"],
        "msa_indexer": [rf"\[{slots},{G},{Di},{nb}\]",
                        rf"\[{slots},{G},{nb}\]",
                        rf"\[{slots},{G},{k['top_blocks']}\]",
                        rf"\[{slots},{G},{J},{Di}\]"],
        "moe_experts": [rf"\[{E},{d},{f}\]", rf"\[{E},{f},{d}\]",
                        rf"\[{E},{f},{slots}\]", rf"\[{E},{slots},{f}\]"],
        "dense": [rf"\[{d},{(H + 2 * G) * D}\]", rf"\[{H * D},{d}\]",
                  rf"\[{d},{G * (J * Di + Di + J)}\]", rf"\[{d},{fd}\]",
                  rf"\[{fd},{d}\]", rf"\[{d},{fs}\]", rf"\[{fs},{d}\]"]}
    kw = config["opcount_sparse"]["kwargs"]
    assert kw == {"heads_a_group": H // G, "head_dim": D, "index_heads": J,
                  "index_dim": Di, "block": k["block"],
                  "itemsize": 2 if k["param_dtype"] == "bfloat16" else 4,
                  "blockkey_itemsize": 4}


# -- the new cell's files say what was asked -------------------------------------
def test_the_serving_cell_is_the_issues(root):
    cell, config, w = cell_mod.load_cell(CELL, root)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "serve-longctx20", 1)
    assert len(cell["why"]) <= 200 and "5 of 60" in cell["why"]
    assert cell in cell_mod.benchmark(root)["workloads"]
    # 20 clients = 20 slots, one a session (a pool padded to 32 would
    # be 10.74 GB of slab beside 6.39 GB of weights)
    assert (w["loop"], w["clients"]) == ("closed", 20)
    assert w["prompt_len"] == {"dist": "lognormal", "median": 8192,
                               "sigma": 0.6, "min": 4096, "max": 24576}
    assert w["output_len"] == {"dist": "lognormal", "median": 2048,
                               "sigma": 0.5, "min": 512, "max": 4096}
    assert w["first_output_scale"] == "uniform" and w["grace_s"] == 0
    assert w["trace_seconds"] == 4 and "engine" not in w
    engine = config["serve"]["engine"]
    assert engine == {"max_sessions": 20, "max_new_tokens": 4096,
                      "prefill_batch": 1}
    assert engine["max_sessions"] == w["clients"]
    k = config["builder"]["kwargs"]
    assert config["builder"]["args"] == [config["vocab_size"]] == [25008]
    assert k["param_dtype"] == config["serve"]["compute_dtype"] == "bfloat16"
    assert config["serve"]["matmul_precision"] == "default"
    # the longest context (24,576 + 4,096) sits on the 32,768 rung
    assert 24576 + 4096 <= 32768 == k["max_len"] \
        == config["max_position_embeddings"]
    for ours, theirs in (("d_model", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("rotary_dim", "rotary_dim"),
                         ("rope_theta", "rope_theta"),
                         ("d_ff", "dense_intermediate_size"),
                         ("d_ff_expert", "intermediate_size"),
                         ("d_ff_shared", "shared_intermediate_size"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("routed_scale", "routed_scaling_factor"),
                         ("swiglu_alpha", "swiglu_alpha"),
                         ("swiglu_limit", "swiglu_limit"),
                         ("norm_eps", "rms_norm_eps"),
                         ("block", "msa_block_size"),
                         ("top_blocks", "msa_top_blocks"),
                         ("index_heads", "msa_index_heads"),
                         ("moe_layers", "moe_layer_freq")):
        assert k[ours] == config[theirs], ours
    assert k["n_experts"] == 128 and k["held"] == [0, 8] \
        == [0, config["num_local_experts"]]
    assert k["rotary_dim"] == config["partial_rotary_factor"] * k["head_dim"]
    ref_kw = config["reference"]["kwargs"]
    assert {**k, **ref_kw} == k and config["reference"]["module"] \
        == "minimax_m3_ref"
    assert config["serve"]["check"]["control"] == {"lower": "float8_e4m3fn"}
    for word in ("indexer_width", "indexer_score", "block_pool",
                 "local_blocks", "rope_pairing", "qk_norm", "router",
                 "shared_expert", "draws", "serve.max_sessions",
                 "serve.prefill_batch"):
        assert word in config["assumed"], word
    assert "16 chips" in config["deployment"]
    assert "vision" in config["published"]["left_out"]
    # the bytes the file states are its own widths'
    by = config["bytes"]
    assert by["parameters"] == 3189346048
    assert by["cache_bytes_context"] == 5 * 20 * 2 * 4 * 128 * 32768 * 2
    assert by["cache_bytes_blockkey"] == 5 * 20 * 4 * 128 * 256 * 4
    assert by["weights_and_slab"] == (by["weights"] + by[
        "cache_bytes_context"] + by["cache_bytes_blockkey"])
    assert 6.38e9 < by["weights"] < 6.39e9


def test_every_width_is_the_catalogs_and_reduced_lists_the_rest(root):
    """Every key of the catalog row's `config` is in the file under the
    same name; the five in `reduced` (depth, which layers route, the
    held experts, the vocabulary slice, the position limit) are the
    only ones that differ, and `published` holds those as the row has
    them. None is a width."""
    row = CATALOG_ROW
    config = config_of(root, CONFIG)
    (entry,) = [c for c in cell_mod.benchmark(root)["configs"]
                if c["name"] == CONFIG]
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    reduced = ["num_hidden_layers", "moe_layer_freq", "num_local_experts",
               "vocab_size", "max_position_embeddings"]
    assert entry["reduced"] == config["reduced"] == reduced
    differ = [key for key, value in row["config"].items()
              if config[key] != value]
    assert sorted(differ) == sorted(reduced)
    assert {key: config["published"][key] for key in reduced} == {
        key: row["config"][key] for key in reduced}
    widths = {"hidden_size", "intermediate_size", "dense_intermediate_size",
              "shared_intermediate_size", "head_dim", "rotary_dim",
              "num_experts_per_tok", "partial_rotary_factor"}
    assert not widths & set(reduced)
    assert not any(key.endswith(("_dim", "_rank")) for key in reduced)


def test_the_new_cell_joins_the_lists_its_metrics_allow(root):
    """The cell reports `out_tokens_per_s` and `setup_s` end to end and
    NOT `tpot_p50_ms` (long prefills between steps, as in the evabyte
    cell: PERF.md section 2), so of the per-layer lists it joins those
    that move what it reports: the serving lists both the hybrid
    model's and the evabyte cell are on, the routed layer's roofline
    and its own five, the held experts' and the dense products' step
    times among them (`moe_experts_step_ms`,
    `blocksparse_dense_step_ms`); not `moe_step_ms` nor
    `expert_load_max_over_mean` (they move `tpot_p50_ms`), nor
    evabyte's `dense_step_ms` (its list is held to cells with
    evabyte's parts by `test_perfbench_evabyte.py`)."""
    bench = cell_mod.benchmark(root)
    mine = {m["name"] for m in cell_mod.metrics_for(CELL, "per_layer", root)}
    both = {m["name"] for m in bench["per_layer"]
            if {"mimo-v2.5-serve-mixedlen", "evabyte-serve-longctx32"}
            <= set(m.get("workloads", []))
            and m["moves"] == "out_tokens_per_s"}
    assert mine == both | set(NEW_METRICS) | {"compiles_in_window",
                                              "moe_experts_roofline_pct"}
    reported = {m["name"]
                for m in cell_mod.metrics_for(CELL, "end_to_end", root)}
    assert reported == {"out_tokens_per_s", "setup_s"}
    moved = {m["moves"] for m in bench["per_layer"] if m["name"] in mine}
    assert moved <= reported
    by_name = {m["name"]: dict(m) for m in bench["per_layer"]}
    for name, unit, better, source, layer in (
            ("attn_sparse_step_ms", "ms", "lower", "device_trace",
             "model math"),
            ("attn_sparse_roofline_pct", "%", "higher", "device_trace",
             "kernels"),
            ("attn_sparse_read_pct", "%", "lower", "program_counter",
             "kernels"),
            ("moe_experts_step_ms", "ms", "lower", "device_trace",
             "model math"),
            ("blocksparse_dense_step_ms", "ms", "lower", "device_trace",
             "model math")):
        entry = by_name[name]
        assert entry.pop("workloads") == [CELL]
        assert entry == {"name": name, "unit": unit, "better": better,
                         "source": source, "layer": layer,
                         "moves": "out_tokens_per_s"}
