"""What ISSUE 37 adds to the benchmark, on the CPU: the eight per-layer
entries that read the program's scopes and step phases
(`harness/scope_trace.py`), on hand-made tuples, on two steps cut out
of a traced `gpt2-train-seq1024` run on the chip with the program's
scope map beside them (`fixtures/v5e_gpt2_train.scopes.json.gz`;
`record_scope_trace.py` says how), and on the scope paths the real
builders give at toy widths. Every test that reads this repository's
files takes its root from the fixture `root`."""
import gzip
import json
import os
import re
import types

import numpy as np
import pytest

from perfbench.harness import cell as cell_mod
from perfbench.harness import scope_trace as st
from perfbench.harness.xplane import Trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_gpt2_train.scopes.json.gz")
TRAINING = ["resnet50-train-dp4", "gpt2-train-seq1024"]
LM, CNN = "gpt2-train-seq1024", "resnet50-train-dp4"
# entry -> (cells, source, layer), as ISSUE 37's table has them
ENTRIES = {
    "step_scoped_pct": (TRAINING, "device_trace", "model math"),
    "optimizer_step_ms": (TRAINING, "device_trace", "model math"),
    "head_loss_step_ms": ([LM], "device_trace", "model math"),
    "attn_layers_step_ms": ([LM], "device_trace", "model math"),
    "bn_step_ms": ([CNN], "device_trace", "model math"),
    "step_dispatch_ms_p50": (TRAINING, "program_span", "trainer step"),
    "step_place_ms_p50": (TRAINING, "program_span", "trainer step"),
    "idle_place_pct": ([CNN], "device_trace", "device"),
}


def _reader(name):
    return cell_mod.module("layer_metrics", name)


def _scope_times():
    from singa_tpu import hlo_profile

    return hlo_profile.scope_times


@pytest.fixture(scope="module")
def cut():
    with gzip.open(FIXTURE, "rt") as f:
        rec = json.load(f)
    rec["devices"] = {int(c): [tuple(o) for o in ops]
                      for c, ops in rec["devices"].items()}
    rec["modules"] = {int(c): [tuple(m) for m in mods]
                      for c, mods in rec["modules"].items()}
    rec["spans"] = [tuple(s) for s in rec["spans"]]
    rec["map"]["instructions"] = {
        k: dict(zip(("shape", "opcode", "scope", "dir"), v))
        for k, v in rec["map"]["instructions"].items()}
    return rec


def _run_of(cut, cell=LM):
    """A run as the train driver leaves it after the traced sub-window,
    with `scope_trace`'s reduction of the excerpt already on it."""
    run = types.SimpleNamespace(
        cell={"name": cell}, device_trace=Trace(devices=cut["devices"]),
        trace_window_ns=tuple(cut["window_ns"]),
        samples={"traced_steps": cut["steps"]}, notes={})
    run.scope_trace = {
        "devices": cut["devices"], "spans": cut["spans"],
        "steps": cut["steps"],
        "scopes": st.reduce(cut["devices"], cut["modules"], [cut["map"]],
                            _scope_times(), *cut["window_ns"])}
    return run


# -- the entries --------------------------------------------------------------
def test_the_eight_entries_are_as_the_issue_lists_them(root):
    bench = cell_mod.benchmark(root)
    cells = [c["name"] for c in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in ENTRIES}
    for name, (want_cells, source, layer) in ENTRIES.items():
        m = by_name[name]
        assert m["workloads"] == sorted(want_cells, key=cells.index), name
        assert (m["source"], m["layer"], m["moves"]) == (
            source, layer, "train_items_per_s"), name
        assert layer in layers        # a name BENCHMARK.json already used
        reader = _reader(name)
        assert (reader.LAYER, reader.UNIT) == (layer, m["unit"])
        assert name in [e["name"] for c in want_cells
                        for e in cell_mod.metrics_for(c, "per_layer", root)]


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_reader_finds_nothing_without_a_trace_or_a_map(name, monkeypatch):
    """The parent's case: no device trace; or a trace, and a program
    with neither the scopes' registry nor the phases."""
    read = _reader(name).read
    bare = types.SimpleNamespace(cell={"name": LM}, device_trace=None,
                                 samples={}, notes={})
    assert read(bare) is None
    traced = types.SimpleNamespace(
        cell={"name": LM}, trace_window_ns=(0, 100), notes={},
        device_trace=Trace(devices={0: [("%a = f32[1]{0} add(", 0, 50)]}),
        samples={"traced_steps": 2})
    traced.scope_trace = {"devices": traced.device_trace.devices,
                          "spans": [], "steps": 2, "scopes": None}
    assert read(traced) is None
    assert traced.notes == {}


def test_step_maps_is_none_where_the_program_has_no_registry(monkeypatch):
    from singa_tpu import hlo_profile

    monkeypatch.delattr(hlo_profile, "step_programs")
    assert st.step_maps() is None
    monkeypatch.undo()
    monkeypatch.setattr(hlo_profile, "step_programs", lambda: [])
    assert st.step_maps() is None


# -- arithmetic on hand-made tuples -------------------------------------------
_MAP = {"module": "jit_step_fn", "scoped": 3, "unscoped": 1, "instructions": {
    "fusion.1": {"shape": "f32[8]", "opcode": "fusion",
                 "scope": "LM.blocks.l0.attn.q_proj/Mult", "dir": "fwd"},
    "flash_fwd.2": {"shape": "bf16[8]", "opcode": "custom-call",
                    "scope": "LM.blocks.l0.attn/Attention", "dir": "bwd"},
    "fusion.3": {"shape": "f32[8]", "opcode": "fusion",
                 "scope": "opt/Adam/LM.embed.W", "dir": ""},
    "copy.4": {"shape": "f32[8]", "opcode": "copy", "scope": "", "dir": ""},
}}


def _step(t):
    return [("%fusion.1 = f32[8]{0} fusion(", t, t + 10),
            ("%flash_fwd.2 = bf16[8]{0} custom-call(", t + 10, t + 40),
            ("%fusion.3 = f32[8]{0} fusion(", t + 40, t + 60),
            ("%copy.4 = f32[8]{0} copy(", t + 60, t + 64)]


def test_reduce_is_the_mean_over_chips_and_keeps_to_the_step_program():
    other = [("%convert.7 = f32[]{} convert(", 220, 226)]
    devices = {0: _step(0) + _step(100) + other,
               1: _step(2) + _step(102)}
    modules = {c: [("jit_step_fn(5)", 0 + 2 * c, 70 + 2 * c),
                   ("jit_step_fn(5)", 100 + 2 * c, 170 + 2 * c),
                   ("jit_convert_element_type(9)", 220, 230)]
               for c in devices}
    red = st.reduce(devices, modules, [_MAP], _scope_times(), 0, 300)
    assert red["rows"] == {
        ("LM.blocks.l0.attn.q_proj/Mult", "fwd"): 20,
        ("LM.blocks.l0.attn/Attention", "bwd"): 60,
        ("opt/Adam/LM.embed.W", ""): 40}
    assert red["unplaced"] == {"not in map": 0, "no scope": 8}
    assert red["unplaced_by_opcode"] == {"copy": 8}
    assert red["total"] == 128 and red["elsewhere"] == 6 / 2
    assert (red["matched"], red["unmatched"]) == (16, 0)
    assert st.under(red, r"^opt/") == 40
    assert st.under(red, r"\.attn[./]") == 80
    assert st.scoped_pct(red) == pytest.approx(100 * 120 / 131)
    line = st.describe(red, 2)
    assert "16 events matched, 0 not" in line
    assert "LM.blocks.l0.attn/Attention bwd 0.000" in line
    assert "by opcode: copy" in line


def test_a_trace_without_the_modules_line_gives_all_to_the_one_map():
    red = st.reduce({0: _step(0)}, {}, [_MAP, _MAP], _scope_times(), 0, 100)
    assert red["total"] == 64 and red["elsewhere"] == 0
    assert sum(red["rows"].values()) == 60


def test_idle_under_a_span_is_by_intersection():
    devices = {0: [("a", 0, 40), ("b", 70, 100)],       # idle 40-70
               1: [("a", 0, 50), ("b", 60, 100)]}       # idle 50-60
    spans = [("step.call", 30, 90), ("step.place", 35, 65),
             ("step.enqueue", 65, 85)]
    got = st.idle_under_pct(devices, spans, "step.place", 0, 100)
    assert got == pytest.approx((25 + 10) / 2)
    assert st.idle_under_pct(devices, spans, "step.bind", 0, 100) == 0.0
    # the note's split adds up to the window's idle share
    line = st.describe_phases(devices, spans + [("step.bind", 85, 88)],
                              [("bench:model(x, y)", 29, 91)], 0, 100)
    assert ("device idle 20.00 % of the window = step.place 17.50 + "
            "step.enqueue 2.50 + step.bind 0.00 + the call's own lines "
            "0.00 + outside the call") in line
    assert "the benchmark's model(x, y) 0.000" in line


# -- two steps cut out of a traced run on the chip ----------------------------
def test_the_cut_reduces_to_what_the_chip_run_printed(cut):
    red = st.reduce(cut["devices"], cut["modules"], [cut["map"]],
                    _scope_times(), *cut["window_ns"])
    printed = cut["printed"]
    assert sorted(([s, d, ns] for (s, d), ns in red["rows"].items()),
                  key=lambda r: -r[2]) == printed["rows"]
    for key in ("unplaced", "unplaced_by_opcode", "total", "elsewhere",
                "matched", "unmatched"):
        assert red[key] == printed[key], key
    assert st.scoped_pct(red) == pytest.approx(printed["scoped_pct"])
    # the shares of a step add up to the step's device time
    placed = sum(red["rows"].values()) + sum(red["unplaced"].values())
    assert placed == pytest.approx(red["total"])
    in_module = sum(t1 - t0 for name, t0, t1 in cut["modules"][0]
                    if name.startswith(cut["map"]["module"] + "("))
    assert red["total"] == pytest.approx(in_module, rel=0.01)


def test_the_cut_is_placed_and_names_its_kernels(cut):
    red = _run_of(cut).scope_trace["scopes"]
    assert st.scoped_pct(red) >= 90
    assert red["unplaced"]["not in map"] == 0
    names = {o[0].split(" = ")[0] for o in cut["devices"][0]}
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv",
                   "softmax_xent_fwd", "softmax_xent_bwd"):
        assert any(re.match(rf"%{kernel}[.\d]*$", n) for n in names), kernel
    # each of the twelve blocks is told from the others, both ways
    for d in ("fwd", "bwd"):
        blocks = {re.search(r"blocks\.l(\d+)\.", s).group(1)
                  for (s, dd) in red["rows"] if dd == d and "blocks.l" in s}
        assert blocks == {str(i) for i in range(12)}, d
    # Adam over the tied embedding is one line
    assert red["rows"][("opt/Adam/TransformerLM.embed.W", "")] > 0


def test_the_lm_cells_readers_read_the_cut(cut):
    run = _run_of(cut)
    red, steps = run.scope_trace["scopes"], cut["steps"]
    got = {name: _reader(name).read(run) for name in ENTRIES
           if LM in ENTRIES[name][0]}
    assert set(got) == {"step_scoped_pct", "optimizer_step_ms",
                        "head_loss_step_ms", "attn_layers_step_ms",
                        "step_dispatch_ms_p50", "step_place_ms_p50"}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["step_scoped_pct"] == pytest.approx(
        cut["printed"]["scoped_pct"])
    step_ms = red["total"] / steps / 1e6
    parts = (got["optimizer_step_ms"] + got["head_loss_step_ms"]
             + got["attn_layers_step_ms"])
    assert 0.3 * step_ms < parts < step_ms
    # the three sum disjoint rows
    for a, b in (("optimizer_step_ms", "head_loss_step_ms"),
                 ("optimizer_step_ms", "attn_layers_step_ms"),
                 ("head_loss_step_ms", "attn_layers_step_ms")):
        ra, rb = (re.compile(_reader(n).SCOPES) for n in (a, b))
        assert not [s for s, _ in red["rows"]
                    if ra.search(s) and rb.search(s)]
    for name, key in (("step_dispatch_ms_p50", st.CALL),
                      ("step_place_ms_p50", st.PLACE)):
        assert got[name] == pytest.approx(cut["printed"]["phase_ms_p50"][key])
    assert got["step_place_ms_p50"] < got["step_dispatch_ms_p50"]


# -- the readers' patterns on the real builders' scope paths ------------------
def _scopes_of_step(model, batch):
    from singa_tpu import hlo_profile

    model.compile([batch[0]], is_train=True, use_graph=True)
    sm = hlo_profile.scope_map(model.step_hlo_text(*batch))
    return {v["scope"] for v in sm["instructions"].values() if v["scope"]}


@pytest.mark.parametrize("root", ["ours"], indirect=True)
def test_the_patterns_match_the_gpt2_builders_paths(root):
    from singa_tpu import tensor

    _, config, _ = cell_mod.load_cell(LM, root)
    spec = dict(config["builder"], args=[97], kwargs=dict(
        config["builder"]["kwargs"], d_model=32, num_heads=2, num_layers=2,
        d_ff=64, max_len=16))
    model = cell_mod.build(spec, root)
    model.set_optimizer(cell_mod.build(config["train"]["optimizer"], root))
    ids = tensor.from_numpy(
        np.random.RandomState(0).randint(0, 97, (4, 16)).astype(np.int32))
    scopes = _scopes_of_step(model, (ids, ids))

    def matched(name):
        rx = re.compile(_reader(name).SCOPES)
        return {s for s in scopes if rx.search(s)}

    assert matched("head_loss_step_ms") == {
        "TransformerLM/Mult", "TransformerLM/Transpose",
        "TransformerLM/Reshape", "TransformerLM/SoftMaxCrossEntropy"} & scopes
    assert {"TransformerLM/Mult", "TransformerLM/SoftMaxCrossEntropy"} <= \
        matched("head_loss_step_ms")
    attn = matched("attn_layers_step_ms")
    for blk in ("l0", "l1"):
        assert f"TransformerLM.blocks.{blk}.attn/Attention" in attn
        assert f"TransformerLM.blocks.{blk}.attn.q_proj/Mult" in attn
        assert f"TransformerLM.blocks.{blk}.attn.o_proj/Mult" in attn
    assert all(".attn" in s for s in attn)
    assert not [s for s in attn if ".fc1" in s or ".ln1" in s]
    opt = matched("optimizer_step_ms")
    assert opt == {s for s in scopes if s.startswith("opt/")}
    assert {f"opt/Adam/{p}" for p in model.get_params()} <= opt
    assert not matched("bn_step_ms")


@pytest.mark.parametrize("root", ["ours"], indirect=True)
def test_the_patterns_match_the_resnet50_builders_paths(root):
    from singa_tpu import tensor

    _, config, _ = cell_mod.load_cell(CNN, root)
    spec = dict(config["builder"], kwargs=dict(
        config["builder"]["kwargs"], num_classes=10))
    model = cell_mod.build(spec, root)
    model.set_optimizer(cell_mod.build(config["train"]["optimizer"], root))
    rs = np.random.RandomState(0)
    x = tensor.from_numpy(rs.randn(2, 3, 32, 32).astype(np.float32))
    y = tensor.from_numpy(rs.randint(0, 10, (2,)).astype(np.int32))
    scopes = _scopes_of_step(model, (x, y))
    rx = re.compile(_reader("bn_step_ms").SCOPES)
    bn = {s for s in scopes if rx.search(s)}
    # the stem's, every block's three and every downsample's: 53
    assert len(bn) == 53, sorted(bn)
    assert {"ResNet.bn1/_BatchNorm2d", "ResNet.layer1.l0.bn3/_BatchNorm2d",
            "ResNet.layer4.l2.bn1/_BatchNorm2d",
            "ResNet.layer2.l0.downsample.bn/_BatchNorm2d"} <= bn
    assert not [s for s in bn if "conv" in s.rsplit("/", 1)[0].split(".")[-1]]
    opt = re.compile(_reader("optimizer_step_ms").SCOPES)
    assert {f"opt/SGD/{p}" for p in model.get_params()} <= {
        s for s in scopes if opt.search(s)}
    for name in ("head_loss_step_ms", "attn_layers_step_ms"):
        rx = re.compile(_reader(name).SCOPES)
        # the classifier and its loss sit under the model, as the LM's do
        assert {s for s in scopes if rx.search(s)} <= {
            "ResNet/SoftMaxCrossEntropy"}, name
