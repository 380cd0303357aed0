"""BENCHMARK.json against the contract's limits, and every name in it
resolves to a file; the command refuses to run off the TPU.

Every check of the file is a function of (bench, root), and every test
takes `root` from `conftest.py`: this repository's, and one grown by
new files and appended entries, held to the same checks."""
import functools
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from perfbench.harness import cell, moe_trace

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def _cells(bench):
    return [w["name"] for w in bench["workloads"]]


@functools.lru_cache(maxsize=None)
def module_of(root, package, name):
    """perfbench/<package>/<name>.py of `root`, loaded from the file
    (this checkout's and a rehearsed root's the same way)."""
    path = os.path.join(root, "perfbench", package, name + ".py")
    assert os.path.isfile(path), f"no perfbench/{package}/{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"_checked_{package}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_of(root, name):
    """perfbench/configs/<name>.json of `root`."""
    with open(os.path.join(root, "perfbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def check_top_level(bench, root):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 65536
    assert bench["paths"] == ["perfbench", "tests/perfbench"]
    assert len(bench["command"]) <= 32 and all(map(_line_ok, bench["command"]))
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with the full 24 cells fits the driver's budget
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def check_config_entry(bench, root, entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and _line_ok(entry["source"])
    assert _line_ok(entry["why"]) and len(entry["reduced"]) <= 16
    assert entry["file"].startswith("perfbench/")
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    assert config["reduced"] == entry["reduced"]
    for key in ("source", "item", "builder", "reference", "opcount"):
        assert key in config, key
    module_of(root, "reference", config["reference"]["module"])
    assert any(w["config"] == entry["name"] for w in bench["workloads"])
    files = [c["file"] for c in bench["configs"]]
    assert files.count(entry["file"]) == 1
    # a decode step's parts, where the file gives them: named as a
    # name is, and expressions that compile once `{slots}` is filled
    parts = config.get("step_parts", {})
    assert all(NAME.match(part) and exprs for part, exprs in parts.items())
    moe_trace.part_patterns(parts, 128)


def check_cell_entry(bench, root, entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(entry[key]), entry[key]
    assert entry["chips"] in (1, 4) and _line_ok(entry["why"])
    c, config, workload = cell.load_cell(entry["name"], root)
    assert workload["driver"] in config        # the driver's section
    module_of(root, "drivers", workload["driver"])
    assert workload["who"] and workload["trace_seconds"] > 0


def check_cells(bench, root):
    cells = _cells(bench)
    assert 2 <= len(cells) <= 24 and len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def check_metric_entry(bench, root, m):
    cells = _cells(bench)
    per_layer = m in bench["per_layer"]
    want = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(m) - {"workloads"} == want
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert set(m.get("workloads", cells)) <= set(cells)
    if per_layer:
        reader = module_of(root, "layer_metrics", m["name"])
        assert callable(reader.read)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            m["layer"], m["unit"], m["moves"])
        moved = {e["name"]: e for e in bench["end_to_end"]}[m["moves"]]
        # reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def check_coverage(bench, root):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in bench["end_to_end"])
    for c in _cells(bench):
        e2e = [m["name"] for m in cell.metrics_for(c, "end_to_end", root)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.metrics_for(c, "per_layer", root)


def check_lists_grow_at_their_end(bench, root):
    """A metric's `workloads` names cells in the order BENCHMARK.json's
    `workloads` has them, each once: a later PR's cell is appended
    there, so it joins every list at its end and moves no entry."""
    cells = _cells(bench)
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", cells)
        assert listed == [c for c in cells if c in listed], m["name"]


def check_all(bench, root):
    """Every check above, over every entry."""
    check_top_level(bench, root)
    for entry in bench["configs"]:
        check_config_entry(bench, root, entry)
    for entry in bench["workloads"]:
        check_cell_entry(bench, root, entry)
    check_cells(bench, root)
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_metric_entry(bench, root, m)
    check_coverage(bench, root)
    check_lists_grow_at_their_end(bench, root)


def test_top_level_keys_and_sizes(root):
    check_top_level(cell.benchmark(root), root)


@pytest.mark.entries("configs")
def test_config_entry(root, entry):
    check_config_entry(cell.benchmark(root), root, entry)


@pytest.mark.entries("workloads")
def test_cell_entry_resolves(root, entry):
    check_cell_entry(cell.benchmark(root), root, entry)


def test_cells_are_unique_and_few_take_four_chips(root):
    check_cells(cell.benchmark(root), root)


@pytest.mark.entries("end_to_end", "per_layer")
def test_metric_entry(root, entry):
    check_metric_entry(cell.benchmark(root), root, entry)


def test_metric_names_are_unique_and_every_cell_is_covered(root):
    check_coverage(cell.benchmark(root), root)


def test_unknown_names_fail_loudly(root):
    with pytest.raises(KeyError, match="no cell"):
        cell.load_cell("no-such-cell", root)
    with pytest.raises(ModuleNotFoundError, match="layer_metrics/nope.py"):
        cell.module("layer_metrics", "nope")


@pytest.mark.entries("workloads", roots=("ours",))
def test_command_refuses_any_platform_but_tpu(root, entry):
    """Exit non-zero and no result line: there is no CPU mode."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", entry["name"],
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no CPU mode" in p.stderr
    assert not any(line.lstrip().startswith("{") for line in
                   p.stdout.splitlines())


@pytest.mark.parametrize("root", ["ours"], indirect=True)
def test_command_refuses_a_directory_without_the_program(root, tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths`: another exit code than 0, and no result."""
    bench = cell.benchmark(root)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(root, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload",
         bench["workloads"][0]["name"],
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "the program is not in this checkout" in p.stderr
    assert p.stdout.strip() == ""
