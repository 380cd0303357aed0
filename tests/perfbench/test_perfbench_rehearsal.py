"""Later PRs' additions, rehearsed: the `grown` root of `conftest.py`
(`additions.py`: a configuration with its cell and two per-layer
metrics, and a second cell on a configuration the benchmark has) joins
the benchmark by NEW files and APPENDED entries alone. Every test of
this directory that reads the repository's BENCHMARK.json or perfbench/
runs on that root too, through the fixture `root`; here are what only
the grown root can show, and the guard that keeps a test file from
stepping round the fixture."""
import ast
import glob
import json
import os
import shutil

import pytest

from additions import (AGAIN_CELL, AGAIN_OF, GPT2_ENTRY, GPT2_METRIC,
                       GPT2_ONLY, GPT2_READER, JOINS, NEW_CELL, NEW_CONFIG,
                       NEW_ENTRY, NEW_METRIC, TOY_MOE, grow, joined)
from perfbench.harness import cell, moe_trace
from test_perfbench_contract import check_all, module_of
from test_perfbench_moe import (
    OTHER, WHILE, check_the_hybrid_cell_reports_what_the_decode_cell_does)
from test_perfbench_program_trace import the_nine

HERE = os.path.dirname(__file__)
# an attention kernel as a trace names it: found by its name, whatever
# shapes its operands have
KERNEL = ('%decode_attend.7 = bf16[4,8,16]{2,1,0} custom-call(bf16[4,2,16,64]'
          '{3,2,1,0} %layer, s32[4]{0} %pos), custom_call_target='
          '"tpu_custom_call"')


@pytest.fixture
def own(grown, tmp_path):
    """A copy of the grown root of the test's own, to rewrite."""
    return str(shutil.copytree(grown, tmp_path / "grown"))


# `root` is the repository's here, beside the grown one
@pytest.mark.parametrize("root", ["ours"], indirect=True)
def test_an_addition_by_new_files_and_appended_entries_passes_every_check(
        root, grown):
    bench = cell.benchmark(grown)
    ours = cell.benchmark(root)
    # what `conftest.py` parametrises over is what the root holds: this
    # repository's entries first, then two cells, two per-layer entries
    assert bench == grow(ours)
    assert bench["workloads"][:-2] == ours["workloads"]
    assert [w["name"] for w in bench["workloads"][-2:]] \
        == [NEW_CELL, AGAIN_CELL]
    assert len(bench["configs"]) == len(ours["configs"]) + 1
    assert bench["per_layer"][-2:] == [NEW_ENTRY, GPT2_ENTRY]
    check_all(bench, grown)
    # the rules the other test files hold this repository's file to
    check_the_hybrid_cell_reports_what_the_decode_cell_does(grown)
    assert all({NEW_CELL, AGAIN_CELL} <= set(m["workloads"])
               for m in the_nine(bench))
    # the new cell resolves to its files, and reports what the cell it
    # joined reports (less that cell's own) and its own metric
    c, config, workload = cell.load_cell(NEW_CELL, grown)
    assert c["config"] == NEW_CONFIG and workload["driver"] in config
    mine = {m["name"] for m in cell.metrics_for(NEW_CELL, "per_layer", grown)}
    theirs = {m["name"] for m in cell.metrics_for(JOINS, "per_layer", grown)}
    assert mine - theirs == {NEW_METRIC} and NEW_METRIC not in theirs
    assert {"idle_readback_pct", "moe_step_ms", "tokens_per_step"} <= mine
    assert module_of(grown, "layer_metrics", NEW_METRIC).MOVES \
        == "tpot_p50_ms"
    # the second cell of a configuration the benchmark has resolves to
    # that configuration and a traffic file of its own, and reports
    # what the first does, the configuration's own metric included
    c, config, workload = cell.load_cell(AGAIN_CELL, grown)
    first, config_of_first, _ = cell.load_cell(AGAIN_OF, grown)
    assert c["config"] == first["config"] and config == config_of_first
    assert c["traffic"] != first["traffic"]
    for group in ("end_to_end", "per_layer"):
        assert cell.metrics_for(AGAIN_CELL, group, grown) \
            == cell.metrics_for(AGAIN_OF, group, grown)
    assert "conv_step_ms" in {
        m["name"] for m in cell.metrics_for(AGAIN_CELL, "per_layer", grown)}
    # nothing this repository's cells report has moved; the two GPT-2
    # serving cells report the metric brought for them, last
    for name in (w["name"] for w in ours["workloads"]):
        for group in ("end_to_end", "per_layer"):
            assert cell.metrics_for(name, group, grown) \
                == [joined(m) for m in cell.metrics_for(name, group, root)] \
                + [GPT2_ENTRY] * (group == "per_layer" and name in GPT2_ONLY)


def _rewrite(root, change):
    bench = cell.benchmark(root)
    change(bench)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


def test_a_metric_for_the_gpt2_cells_alone_brings_its_own_exception(own):
    """The rule that the hybrid model's cell reports what
    `gpt2-serve-decode` does takes its exception from the reader's own
    file, so it passes the root that a PR such as PR 30 would leave;
    and it is a rule still: the same entry with a reader that names no
    counter, or only counters the hybrid model counts, is refused."""
    check_the_hybrid_cell_reports_what_the_decode_cell_does(own)
    mine = {m["name"] for m in cell.metrics_for(JOINS, "per_layer", own)}
    assert GPT2_METRIC not in mine and "attn_rung_read_pct" not in mine
    reader = os.path.join(own, "perfbench", "layer_metrics",
                          GPT2_METRIC + ".py")
    for counters in ('()', '("moe_experts_touched",)'):
        with open(reader, "w") as f:
            f.write(GPT2_READER.replace('("attn_blocks_read",)', counters))
        module_of.cache_clear()
        with pytest.raises(AssertionError, match=GPT2_METRIC):
            check_the_hybrid_cell_reports_what_the_decode_cell_does(own)
    # a reader of the device trace finds one model's operations: free
    _rewrite(own, lambda b: [m.update(source="device_trace")
                             for m in b["per_layer"]
                             if m["name"] == GPT2_METRIC])
    check_the_hybrid_cell_reports_what_the_decode_cell_does(own)
    # and a span reader that `serve.py` feeds under either model is not:
    # taking the hybrid cell off one of the nine is refused
    _rewrite(own, lambda b: [m["workloads"].remove(JOINS)
                             for m in b["per_layer"]
                             if m["name"] == "token_scatter_ms_p50"])
    with pytest.raises(AssertionError, match="token_scatter_ms_p50"):
        check_the_hybrid_cell_reports_what_the_decode_cell_does(own)


def test_a_fourth_part_found_by_name_is_summed_like_the_three(grown):
    """`moe_trace.reduce` over the grown root's configuration: the
    kernel's operations go to the part that names it, though their
    operands carry the shapes of another part; the three parts found
    by shape read what they read without it."""
    c, config, workload = cell.load_cell(NEW_CELL, grown)
    run = cell.Run(cell=c, config=config, workload=workload, seconds=1.0,
                   trace=True)
    rx = moe_trace.patterns_of(run)         # 4 slots from the toy's engine
    assert list(rx) == ["attn_kernel", "moe_experts", "attn_full",
                        "attn_window"]
    assert rx["attn_full"].search(KERNEL)   # by shape it would go there

    mods = [("jit_slot_step(123)", 0, 1000), ("jit_prefill_rows(5)", 1000,
                                              3000),
            ("jit_slot_scan_4(77)", 3000, 8000)]
    gate = "%f.7 = f32[4,4,32]{2,1,0} fusion(f32[4,48]{1,0} %x, " \
        "f32[4,48,32]{2,1,0} %W_g), kind=kOutput"
    ops = [(gate, 100, 300), (KERNEL, 300, 450), (OTHER, 450, 500),
           (KERNEL, 1500, 2500),               # a prefill's: not a step's
           (WHILE, 3000, 8000), (KERNEL, 3100, 3500), (gate, 3500, 3900)]
    red = moe_trace.reduce(ops, mods, rx, 0, 10000)
    assert red["steps"] == 5
    assert red["seconds"] == {
        "attn_kernel": pytest.approx(550e-9),
        "moe_experts": pytest.approx(600e-9), "attn_full": 0.0,
        "attn_window": 0.0}
    run.moe_trace = red
    reader = module_of(grown, "layer_metrics", NEW_METRIC)
    assert reader.read(run) == pytest.approx(1e3 * 550e-9 / 5)
    # the same tuples through the configuration without the part
    ours = moe_trace.part_patterns(json.load(open(os.path.join(
        TOY_MOE, "configs", "toy_moe.json")))["step_parts"], 4)
    red = moe_trace.reduce(ops, mods, ours, 0, 10000)
    assert red["seconds"]["attn_full"] == pytest.approx(550e-9)
    assert red["seconds"]["moe_experts"] == pytest.approx(600e-9)


# -- the guard: no test file steps round the fixture -----------------------------
# positional arguments before `root` of the functions of `harness/cell.py`
# that read a root, which default it to the repository
ROOTED = {"benchmark": 0, "load_cell": 1, "metrics_for": 2,
          "resolve_callable": 1, "build": 1}


def rootless(source, filename="<planted>"):
    """"file:line: what" for every call in `source` of one of `ROOTED`
    that passes no root, and for every use of `ROOT`: either reads this
    repository whatever root the test runs on."""
    found = []
    for node in ast.walk(ast.parse(source, filename)):
        what = None
        if isinstance(node, ast.Call):
            f = node.func
            name = f.attr if isinstance(f, ast.Attribute) else getattr(
                f, "id", None)
            if name in ROOTED and len(node.args) <= ROOTED[name] \
                    and not any(k.arg in ("root", None)
                                for k in node.keywords):
                what = f"{name}() with no root"
        elif isinstance(node, ast.Attribute) and node.attr == "ROOT":
            what = "ROOT"
        elif isinstance(node, ast.ImportFrom) and any(
                a.name == "ROOT" for a in node.names):
            what = "ROOT"
        if what:
            found.append(f"{filename}:{node.lineno}: {what}")
    return found


@pytest.mark.parametrize("planted, refused", [
    ("bench = cell_mod.benchmark()", True),
    ("bench = cell_mod.benchmark(root)", False),
    ("c, config, w = cell.load_cell(NAME)", True),
    ("c, config, w = cell.load_cell(NAME, root)", False),
    ("c, config, w = load_cell(NAME, root=TOY)", False),
    ("ms = cell.metrics_for(NAME, 'per_layer')", True),
    ("model = cell.resolve_callable(spec)", True),
    ("model = cell.build(spec, root)", False),
    ("path = os.path.join(cell.ROOT, 'perfbench')", True),
    ("from perfbench.harness.cell import ROOT", True),
    ("def counted_by(config, root=cell_mod.ROOT): pass", True),
], ids=lambda v: v if isinstance(v, str) else ("passes", "refused")[v])
def test_the_guard_refuses_a_planted_read_of_the_repository(planted, refused):
    assert bool(rootless(planted)) == refused, rootless(planted)


def test_every_test_file_takes_its_root_from_the_fixture():
    """Every `test_*.py` of this directory, the files later PRs bring
    too: a test that reads the repository's benchmark does so through
    `root` (conftest.py), so that it runs on the grown root in the PR
    that writes it. `conftest.py` alone names the repository."""
    files = sorted(glob.glob(os.path.join(HERE, "test_*.py")))
    assert os.path.abspath(__file__) in files
    found = []
    for path in files:
        with open(path) as f:
            found += rootless(f.read(), os.path.relpath(path, HERE))
    assert not found, "\n".join(found)
