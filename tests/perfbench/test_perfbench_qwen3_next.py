"""What the Qwen3-Next configuration adds to the benchmark, on the CPU:
the serving driver drives the Gated DeltaNet model at toy size through
to a result line (the toy cell lives under tests/perfbench/
toy_qwen3_next/, a root of its own: never a cell, never reachable from
the command); the addition is data the harness reads by name, with no
edit to a file it had; `gdn_step_ms`, `gdn_roofline_pct` and the three
step times beside them read their parts and counter; the new cell's
files say what was asked of them; the control, and the two planted
faults of the state, come out not correct.
Every test that reads this repository's files takes its root from the
fixture `root`."""
import os
import time

import jax
import pytest

from perfbench import run as run_mod
from perfbench.harness import cell as cell_mod
from perfbench.harness import moe_trace, opcount, opcount_gdn, peaks
from perfbench.harness import profiler, traffic
from perfbench.layer_metrics import (attn_gated_step_ms, gdn_dense_step_ms,
                                     gdn_experts_step_ms, gdn_roofline_pct,
                                     gdn_step_ms, moe_experts_roofline_pct)
from perfbench.reference import qwen3_next_control
from test_perfbench_contract import config_of
from test_perfbench_drivers import FakeDeviceTrace, _meter, policies  # noqa: F401

TOY = os.path.join(os.path.dirname(__file__), "toy_qwen3_next")
CELL, CONFIG = "qwen3-next-serve-decode256", "qwen3-next-80b-a3b"
NEW_METRICS = ("gdn_step_ms", "gdn_roofline_pct", "attn_gated_step_ms",
               "gdn_dense_step_ms", "gdn_experts_step_ms")
# the published configuration of `Qwen3-Next-80B-A3B-Instruct`: its
# `source_url` and every key of its
# `config`, written out so that the test reads nothing outside the
# checkout
CATALOG_ROW = {
    "source_url": "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/"
                  "blob/main/config.json",
    "config": {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}}
V5E = peaks.for_kind("TPU v5 lite")


def _toy_run(trace, seconds=1.0):
    cell, config, workload = cell_mod.load_cell("toy-qwen3-next-serve", TOY)
    return cell_mod.Run(cell=cell, config=config, workload=workload,
                        seconds=seconds, trace=trace, seed=2147483951,
                        t_process_start=time.perf_counter(), meter=_meter(),
                        peaks=V5E)


def test_the_serving_driver_serves_the_model_and_counts_its_states(
        monkeypatch, policies):  # noqa: F811
    """A traced run: the result line's end-to-end metrics and a check
    the reference passes; with no device trace file on the CPU the
    readers of device time find nothing and are left out (as on a
    program that lacks the parts); the counters and the gauges are the
    model's own."""
    from singa_tpu import stats

    monkeypatch.setattr(profiler, "DeviceTrace", FakeDeviceTrace)
    run = _toy_run(True)
    driver = cell_mod.module("drivers", run.workload["driver"])
    driver.run(run)
    line = run_mod.result_line(run, jax.devices()[:1],
                               driver.UNATTRIBUTED_GAP, TOY)
    assert line["correct"] is True, run.wrong
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compared"]["served_token_under_reference_best"][
        "value"] <= 0.001
    m = line["metrics"]
    assert not set(NEW_METRICS) & set(m)
    assert {"tokens_per_step", "compiles_in_window",
            "serve_device_idle_pct"} <= set(m)
    d = run.counters["decode"]
    gauges = stats.cache_stats()["decode"]
    # 3 Gated DeltaNet layers x 4 slots x 4 heads x 8 x 16 float32; the
    # convolution's 3 last inputs of 2 x 8 + 2 x 8 + 4 x 16 channels
    assert gauges["cache_bytes_recurrent"] == 3 * 4 * 4 * 8 * 16 * 4
    assert gauges["cache_bytes_state"] == 3 * 4 * 3 * 96 * 4
    assert gauges["cache_bytes_context"] % (4 * 2 * 2 * 16 * 4) == 0
    assert d["gdn_state_rows"] == 3 * 4 * d["decode_steps"] > 0


def test_the_control_tier_comes_out_not_correct(policies):  # noqa: F811
    """The margin's two sides through the harness's own comparison: the
    served tokens pass it, the greedy choice of the reference computed
    one precision below the configuration's (bfloat16 under the toy's
    float32) does not; the matrix states a slab row holds after the
    longest context (120 + 40 positions) are the reference
    recurrence's to float32 rounding."""
    out = qwen3_next_control.run(_toy_run(False, seconds=0.0))
    assert out["streams"] == 4 and out["lower"] == "bfloat16"
    assert out["served_correct"] and out["served_worst"] <= 0.001
    assert not out["control_correct"] and out["control_worst"] > 0.01
    assert out["state_positions"] == 160
    assert out["state_worst"] < 1e-5 < out["state_limit"] == 1e-3


@pytest.mark.parametrize("fault", qwen3_next_control.FAULTS)
def test_a_planted_fault_of_the_state_comes_out_not_correct(
        fault, monkeypatch, policies):  # noqa: F811
    from singa_tpu.models.gated_delta_moe import GatedDeltaMoELM

    for name in ("new_slab", "_prefill_rows"):
        monkeypatch.setattr(GatedDeltaMoELM, name,
                            GatedDeltaMoELM.__dict__[name])
    """Each fault moves the states a slab row holds past the state
    reading's limit, by 30 times and more (a bfloat16 state, rounded at
    every step, 0.03; a prompt's state dropped, 0.75), so the control
    serves it no further."""
    out = qwen3_next_control.run(_toy_run(False, seconds=0.0), fault=fault)
    assert out["fault"] == fault
    assert out["state_worst"] > 20 * out["state_limit"]
    assert out["state_worst"] == max(out["state_worst_by_layer"])
    assert "served_correct" not in out


# -- the addition is data -----------------------------------------------------
def test_the_addition_is_new_files_the_harness_reads_by_name(root):
    """The cell, its configuration, its traffic and its two metrics
    resolve by name through the harness as it was: no file of the
    harness, the drivers or the command names the model, the cell or a
    new metric; the generator reads the traffic file's keys; each new
    metric's reader states what its entry states."""
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for sub in ("perfbench/harness", "perfbench/drivers", "perfbench/run.py"):
        path = os.path.join(here, sub)
        files = ([os.path.join(path, f) for f in os.listdir(path)
                  if f.endswith(".py") and f != "opcount_gdn.py"]
                 if os.path.isdir(path) else [path])
        for name in files:
            text = open(name).read()
            for word in ("qwen", "gated_delta", "gdn_", *NEW_METRICS):
                assert word not in text, (name, word)
    cell, config, w = cell_mod.load_cell(CELL, root)
    assert traffic.prompt_buckets(w) == [256, 512, 1024, 2048]
    assert traffic.limits(w) == (256, 2048, 2048)
    by_name = {m["name"]: m for m in cell_mod.benchmark(root)["per_layer"]}
    for name in NEW_METRICS:
        reader = cell_mod.module("layer_metrics", name)
        entry = by_name[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            entry["layer"], entry["unit"], entry["moves"])
        assert entry["workloads"] == [CELL]
    model = cell_mod.resolve_callable(config["builder"]["callable"], root)
    assert set(gdn_roofline_pct.COUNTERS) <= set(model.step_counter_names)


# -- the device-time readers, on tuples ---------------------------------------
# instruction texts of the decode step as the compiler writes it for a
# v5e at the cell's geometry (tests/test_tpu_compile_widths.py), with
# their operands' types as a trace's event names carry them: the delta
# rule's kernel and the packing of its inputs, the convolution state's
# update, the gated norm, the context's write and scores, a held
# expert's gate product, the fused q/k/v/z projection, and what belongs
# to no part: the head
KERNEL = ("%gated_delta_step.12 = (f32[256,32,128], f32[256,32,128,128]) "
          "custom-call(f32[256,64,128] %bitcast.931, f32[256,96,128] "
          "%copy.833, f32[256,32,128,128] %c_0___S__.1), "
          'custom_call_target="tpu_custom_call"')
PACK = ("%copy.983 = f32[256,96,128] copy(f32[256,32,3,128] "
        "%pad_maximum_fusion.1)")
CONV = ("%fusion.300 = (bf16[256,3,8192], f32[256,8192]) fusion(bf16[256,3,"
        "8192] %c_0___u__.1, bf16[256,1,16,512] %copy.992, f32[1,4,16,512] "
        "%fusion.301), kind=kLoop")
NORM = ("%fusion.310 = f32[256,32,128] fusion(f32[256,32,128] "
        "%get-tuple-element.12, f32[256,32] %fusion.309), kind=kLoop")
WRITE = ("%cache_write.4 = bf16[256,2,256,4096] custom-call(s32[256] %po.1, "
         "bf16[256,2,256,4096] %c_3___k__.1, bf16[256,2,256,1] %copy.997), "
         'custom_call_target="tpu_custom_call"')
SCORES = ("%fusion.34 = f32[256,2,8,4096] fusion(bf16[256,2,8,256] "
          "%copy.983, bf16[256,2,256,4096] %cache_write.4, pred[256,4096] "
          "%iota_compare_fusion), kind=kOutput")
GATE = ("%fusion.157 = bf16[32,512,256] fusion(bf16[32,2048,512] "
        "%p__blocks___1___ffn____W_g__.1, bf16[256,2048] %fusion.13), "
        "kind=kOutput")
QKVZ = ("%convolution_bitcast_fusion.5 = bf16[256,1,16,768] fusion(bf16[2048,"
        "12288] %p__blocks___0___mix____W_qkvz__.1, bf16[256,2048] %fusion.8),"
        " kind=kOutput")
HEAD = ("%fusion.9 = f32[256,18992] fusion(bf16[2048,18992] %p__head__.1, "
        "bf16[256,2048] %fusion.8), kind=kOutput")


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
    ops = [(QKVZ, 0, 20), (CONV, 20, 30), (PACK, 30, 35), (KERNEL, 35, 135),
           (NORM, 135, 140), (WRITE, 140, 150), (SCORES, 150, 170),
           (GATE, 170, 270), (HEAD, 270, 300),
           (KERNEL, 1500, 2500),               # a prefill's: not a step's
           (KERNEL, 3000, 3800), (SCORES, 3800, 4200), (GATE, 4200, 4400)]
    config = config_of(root, CONFIG)
    rx = moe_trace.part_patterns(config["step_parts"], 256)
    assert set(rx) == {"attn_full", "moe_experts", "gdn", "dense"}
    red = moe_trace.reduce(ops, mods, rx, 0, 20000)
    assert red["steps"] == 9
    assert red["seconds"]["gdn"] == pytest.approx(920e-9)
    assert red["seconds"]["attn_full"] == pytest.approx(430e-9)
    assert red["seconds"]["moe_experts"] == pytest.approx(300e-9)
    assert red["seconds"]["dense"] == pytest.approx(20e-9)
    run = _traced(config, 9, red["seconds"])
    for reader, part in ((gdn_step_ms, "gdn"), (attn_gated_step_ms,
                                                "attn_full"),
                         (gdn_dense_step_ms, "dense"),
                         (gdn_experts_step_ms, "moe_experts")):
        assert reader.read(run) == pytest.approx(
            1e3 * red["seconds"][part] / 9)
    # a configuration without the part (the minimax model's), or a run
    # without a trace, leaves the readers with nothing: the metrics are
    # left out, as on the parent of the PR that added them
    other_config = config_of(root, "minimax-m3")
    other = cell_mod.Run(cell={"name": "x"}, config=other_config,
                         workload={}, seconds=30.0, trace=True, peaks=V5E)
    other.moe_trace = moe_trace.reduce(ops, mods, moe_trace.part_patterns(
        other_config["step_parts"], 32), 0, 20000)
    bare = cell_mod.Run(cell={"name": CELL}, config=config, workload={},
                        seconds=30.0, trace=False, peaks=V5E)
    for reader in (gdn_step_ms, gdn_roofline_pct):
        assert reader.read(other) is None
    for reader in (gdn_step_ms, gdn_roofline_pct, attn_gated_step_ms,
                   gdn_dense_step_ms, gdn_experts_step_ms):
        assert reader.read(bare) is None


def test_the_roofline_share_is_what_the_states_needed_over_what_they_took(
        root):
    """256 rows x 6 layers a step, each 32 states of 128 x 128 float32
    read and written once: 3.22 GB a step, the least time memory's. A
    part that took that at 80 % of the HBM's peak reads 80 % (less the
    q, k, v, g and beta the count adds); one that moved every state
    twice reads under half that."""
    config = config_of(root, CONFIG)
    spec = config["opcount_gdn"]
    assert spec["module"] == "opcount_gdn"
    ops, nbytes = getattr(opcount_gdn, spec["function"])(1, **spec["kwargs"])
    assert nbytes == 32 * (2 * 128 * 128 * 4 + (2 * 128 + 128 + 2) * 4)
    assert 2 * 32 * 128 * 128 * 4 == 2 * config["bytes"]["slot_state_a_layer"]
    assert opcount.roofline_seconds(ops, nbytes, V5E)[1] == "memory"
    rows, steps = 256 * 6, 100
    states = rows * 2 * config["bytes"]["slot_state_a_layer"]
    took = steps * states / (0.8 * V5E["hbm_bytes_per_s"])
    run = _traced(config, steps, {"gdn": took, "dense": 0.3})
    run.counters["decode"] = {"decode_steps": 1000,
                              "gdn_state_rows": 1000 * rows}
    share = gdn_roofline_pct.read(run)
    assert share == pytest.approx(80.0 * nbytes * rows / states)
    assert 80.0 < share < 81.0
    assert "memory-bound" in run.notes["gdn_roofline"]
    twice = _traced(config, steps, {"gdn": 2 * took})
    twice.counters = run.counters
    assert gdn_roofline_pct.read(twice) < 41.0
    # a program that counts no state (another model's) reads nothing
    run.counters["decode"] = {"decode_steps": 1000}
    assert gdn_roofline_pct.read(run) is None
    assert moe_experts_roofline_pct.read(run) is None


@pytest.mark.parametrize("name", [CONFIG, "toy_qwen3_next"])
def test_the_files_parts_and_counts_are_its_own_widths(root, name):
    """`step_parts` and `opcount_gdn` are written out, not derived, so
    hold them to the widths the same file gives the model: the kernel's
    and the cache write's names, the kernel's packed inputs [slots,
    Hv, 2|3, Dk] / [slots, 2 Hv, Dk] / [slots, 3 Hv, Dv], its output
    [slots, Hv, Dv], the convolution's state [slots, L-1, C]; the
    context's scores and arrays [slots, Hkv, Hg | D, T]; the held
    experts' matrices; the projections."""
    config = config_of(TOY if name == "toy_qwen3_next" else root, name)
    k = config["builder"]["kwargs"]
    Hv, Dk, Dv = k["lin_v_heads"], k["lin_k_dim"], k["lin_v_dim"]
    G, H, D = k["kv_heads"], k["num_heads"], k["head_dim"]
    C = 2 * k["lin_k_heads"] * Dk + Hv * Dv
    parts = config["step_parts"]
    assert parts["gdn"][0] == r"^%gated_delta_step[.\d]* = "
    assert parts["attn_full"][:3] == [r"^%cache_write[.\d]* = ",
                                      rf"\[{{slots}},{G},{H // G},\d+\]",
                                      rf"\[{{slots}},{G},{D},\d+\]"]
    assert rf"\[{{slots}},{k['conv_L'] - 1},{C}\]" in parts["gdn"]
    assert rf"\[{{slots}},{Hv},{Dv}\]" in parts["gdn"]
    E, d, f = k["held"][1], k["d_model"], k["d_ff_expert"]
    assert parts["moe_experts"] == [
        rf"\[{E},{d},{f}\]", rf"\[{E},{f},{d}\]", rf"\[{E},{f},{{slots}}\]",
        rf"\[{E},{{slots}},{f}\]"]
    assert config["opcount_gdn"]["kwargs"] == {
        "value_heads": Hv, "key_dim": Dk, "value_dim": Dv,
        "state_itemsize": 4, "io_itemsize": 4}
    assert config["opcount"]["kwargs"] == {
        "d_model": d, "d_ff_expert": f,
        "itemsize": 2 if k["param_dtype"] == "bfloat16" else 4}


# -- the new cell's files say what was asked ----------------------------------
def test_the_serving_cell_is_the_issues(root):
    cell, config, w = cell_mod.load_cell(CELL, root)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "serve-decode256", 1)
    assert len(cell["why"]) <= 200 and "8 of 48" in cell["why"]
    assert cell in cell_mod.benchmark(root)["workloads"]
    assert (w["loop"], w["clients"]) == ("closed", 256)
    assert w["prompt_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 0.6, "min": 256, "max": 2048}
    assert w["output_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 0.5, "min": 256, "max": 2048}
    assert w["first_output_scale"] == "uniform" and w["grace_s"] == 0
    assert "engine" not in w and not traffic.samplers(w)
    engine = config["serve"]["engine"]
    assert engine == {"max_sessions": 256, "max_new_tokens": 2048,
                      "prefill_batch": 2}
    assert engine["max_sessions"] == w["clients"]
    k = config["builder"]["kwargs"]
    assert config["builder"]["args"] == [config["vocab_size"]] == [18992]
    assert k["param_dtype"] == config["serve"]["compute_dtype"] == "bfloat16"
    # the longest context (2,048 + 2,048) sits on the 4,096 rung
    assert 2048 + 2048 <= 4096 == k["max_len"] \
        == config["max_position_embeddings"]
    for ours, theirs in (("d_model", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("rope_theta", "rope_theta"),
                         ("lin_k_heads", "linear_num_key_heads"),
                         ("lin_v_heads", "linear_num_value_heads"),
                         ("lin_k_dim", "linear_key_head_dim"),
                         ("lin_v_dim", "linear_value_head_dim"),
                         ("conv_L", "linear_conv_kernel_dim"),
                         ("d_ff_expert", "moe_intermediate_size"),
                         ("d_ff_shared", "shared_expert_intermediate_size"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("full_interval", "full_attention_interval"),
                         ("num_layers", "num_hidden_layers"),
                         ("norm_eps", "rms_norm_eps")):
        assert k[ours] == config[theirs], ours
    assert k["n_experts"] == 512 == config["published"]["num_experts"]
    assert k["held"] == [0, config["num_experts"]] == [0, 32]
    assert k["rotary_dim"] == config["partial_rotary_factor"] * k["head_dim"]
    assert (config["decoder_sparse_step"], config["mlp_only_layers"]) == (1,
                                                                          [])
    ref_kw = config["reference"]["kwargs"]
    assert {**k, **ref_kw} == k and config["reference"]["module"] \
        == "qwen3_next_ref"
    assert config["serve"]["check"]["control"] == {"lower": "float8_e4m3fn"}
    assert config["serve"]["check"]["streams"] == 4
    for word in ("decay_draw", "draws", "norms", "rope_pairing",
                 "attention_gate",
                 "qkvz_grouping", "router", "shared_expert",
                 "delta_precision", "serve.max_sessions",
                 "serve.prefill_batch"):
        assert word in config["assumed"], word
    assert "16 chips" in config["deployment"]
    assert "multi-token-prediction" in config["published"]["left_out"]


def test_the_bytes_are_the_files_own_widths(root):
    """Every number of the `bytes` section recomputed from the widths
    the file gives the model: weights and slab fill 62 % of the chip."""
    config = config_of(root, CONFIG)
    k, by = config["builder"]["kwargs"], config["bytes"]
    d, V = k["d_model"], config["vocab_size"]
    Hk, Hv, Dk, Dv = (k["lin_k_heads"], k["lin_v_heads"], k["lin_k_dim"],
                      k["lin_v_dim"])
    H, G, D, L = k["num_heads"], k["kv_heads"], k["head_dim"], k["conv_L"]
    E, f, fs = k["held"][1], k["d_ff_expert"], k["d_ff_shared"]
    C = 2 * Hk * Dk + Hv * Dv
    gdn = (d * (2 * Hk * Dk + 2 * Hv * Dv) + d * 2 * Hv + L * C
           + Hv * Dv * d)
    assert by["gated_deltanet_layer"] == 2 * gdn + 4 * (2 * Hv + Dv)
    full = d * (2 * H * D + 2 * G * D) + H * D * d
    assert by["full_attention_layer"] == 2 * full + 4 * 2 * D
    assert by["held_experts_a_layer"] == 2 * E * 3 * d * f
    assert by["shared_expert_a_layer"] == 2 * (3 * d * fs + d)
    assert by["router_a_layer"] == 4 * d * k["n_experts"]
    assert by["vocabulary_slice"] == 2 * 2 * V * d
    layers = k["num_layers"]
    n_full = layers // k["full_interval"]
    assert by["weights"] == (
        (layers - n_full) * by["gated_deltanet_layer"]
        + n_full * by["full_attention_layer"]
        + layers * (by["held_experts_a_layer"] + by["shared_expert_a_layer"]
                    + by["router_a_layer"] + 2 * 4 * d)
        + by["vocabulary_slice"] + 4 * d)
    slots, rung = config["serve"]["engine"]["max_sessions"], k["max_len"]
    assert by["slot_state_a_layer"] == Hv * Dk * Dv * 4
    assert by["cache_bytes_recurrent"] == (
        (layers - n_full) * slots * by["slot_state_a_layer"])
    assert by["cache_bytes_state"] == (layers - n_full) * slots * (L - 1) \
        * C * 2
    assert by["cache_bytes_context"] == n_full * slots * 2 * G * D * rung * 2
    assert by["weights_and_slab"] == by["weights"] + sum(
        by[f"cache_bytes_{kind}"] for kind in ("recurrent", "state",
                                               "context"))
    assert 0.6 < by["weights_and_slab"] / 16e9 < 0.65


def test_every_width_is_the_catalogs_and_reduced_lists_the_rest(root):
    """Every key of the catalog row's `config` is in the file under the
    same name; the four in `reduced` (depth, the held experts, the
    vocabulary slice, the position limit) are the only ones that
    differ, and `published` holds those as the row has them. None is a
    width."""
    row = CATALOG_ROW
    config = config_of(root, CONFIG)
    (entry,) = [c for c in cell_mod.benchmark(root)["configs"]
                if c["name"] == CONFIG]
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    reduced = ["num_hidden_layers", "num_experts", "vocab_size",
               "max_position_embeddings"]
    assert set(entry["reduced"]) == set(config["reduced"]) == set(reduced)
    differ = [key for key, value in row["config"].items()
              if config[key] != value]
    assert set(differ) == set(reduced)
    assert {key: config["published"][key] for key in reduced} == {
        key: row["config"][key] for key in reduced}
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "shared_expert_intermediate_size", "head_dim",
              "linear_key_head_dim", "linear_value_head_dim",
              "num_experts_per_tok", "partial_rotary_factor"}
    assert not widths & set(reduced)
    assert not any(key.endswith(("_dim", "_rank")) for key in reduced)


def test_the_new_cell_joins_the_lists_its_metrics_allow(root):
    """The cell reports `out_tokens_per_s` and `setup_s` end to end, so
    of the per-layer lists it joins those that move what it reports:
    the serving lists every drawn model's cell is on, the routed layer's
    roofline, and its own five: the held experts' step time is read by
    `gdn_experts_step_ms`, `moe_experts_step_ms` staying the block-sparse
    model's alone."""
    bench = cell_mod.benchmark(root)
    mine = {m["name"] for m in cell_mod.metrics_for(CELL, "per_layer", root)}
    assert {"tokens_per_step", "serve_device_idle_pct", "serve_peak_hbm_gb",
            "idle_readback_pct", "idle_dispatch_pct", "idle_hostwork_pct",
            "idle_nowork_pct", "idle_unattributed_pct",
            "steps_tokens_only_pct", "steps_chained_pct",
            "moe_experts_roofline_pct", "compiles_in_window",
            *NEW_METRICS} <= mine
    assert not {"moe_experts_step_ms", "attn_full_step_ms",
                "dense_step_ms"} & mine
    reported = {m["name"]
                for m in cell_mod.metrics_for(CELL, "end_to_end", root)}
    assert {"out_tokens_per_s", "setup_s"} <= reported
    moved = {m["moves"] for m in bench["per_layer"] if m["name"] in mine}
    assert moved <= reported
    by_name = {m["name"]: dict(m) for m in bench["per_layer"]}
    for name, unit, better, layer in (
            ("gdn_step_ms", "ms", "lower", "model math"),
            ("gdn_roofline_pct", "%", "higher", "kernels"),
            ("attn_gated_step_ms", "ms", "lower", "model math"),
            ("gdn_dense_step_ms", "ms", "lower", "model math"),
            ("gdn_experts_step_ms", "ms", "lower", "model math")):
        entry = by_name[name]
        assert entry.pop("workloads") == [CELL]
        assert entry == {"name": name, "unit": unit, "better": better,
                         "source": "device_trace", "layer": layer,
                         "moves": "out_tokens_per_s"}
