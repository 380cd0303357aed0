"""BENCHMARK.json against the contract's limits, and every name in it
resolves to a file; the command refuses to run off the TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from perfbench.harness import cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = cell.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(cell.ROOT, "BENCHMARK.json")) < 65536
    assert BENCH["paths"] == ["perfbench", "tests/perfbench"]
    assert len(BENCH["command"]) <= 32 and all(map(_line_ok, BENCH["command"]))
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check with the full 24 cells fits the driver's budget
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and _line_ok(entry["source"])
    assert _line_ok(entry["why"]) and len(entry["reduced"]) <= 16
    assert entry["file"].startswith("perfbench/")
    with open(os.path.join(cell.ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["reduced"] == entry["reduced"]
    for key in ("source", "item", "builder", "reference", "opcount"):
        assert key in config, key
    cell.module("reference", config["reference"]["module"])
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_resolves(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(entry[key]), entry[key]
    assert entry["chips"] in (1, 4) and _line_ok(entry["why"])
    c, config, workload = cell.load_cell(entry["name"])
    assert workload["driver"] in config        # the driver's section
    cell.module("drivers", workload["driver"])
    assert workload["who"] and workload["trace_seconds"] > 0


def test_cells_are_unique_and_few_take_four_chips():
    assert 2 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    per_layer = m in BENCH["per_layer"]
    want = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(m) - {"workloads"} == want
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    if per_layer:
        reader = cell.module("layer_metrics", m["name"])
        assert callable(reader.read)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            m["layer"], m["unit"], m["moves"])
        moved = {e["name"]: e for e in BENCH["end_to_end"]}[m["moves"]]
        # reported only where the metric it moves is
        assert set(m.get("workloads", CELLS)) <= set(
            moved.get("workloads", CELLS))
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def test_metric_names_are_unique_and_every_cell_is_covered():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])
    for c in CELLS:
        e2e = [m["name"] for m in cell.metrics_for(c, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.metrics_for(c, "per_layer")


def test_unknown_names_fail_loudly():
    with pytest.raises(KeyError, match="no cell"):
        cell.load_cell("no-such-cell")
    with pytest.raises(ModuleNotFoundError, match="layer_metrics/nope.py"):
        cell.module("layer_metrics", "nope")


@pytest.mark.parametrize("name", CELLS)
def test_command_refuses_any_platform_but_tpu(name):
    """Exit non-zero and no result line: there is no CPU mode."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", name,
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=cell.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no CPU mode" in p.stderr
    assert not any(line.lstrip().startswith("{") for line in
                   p.stdout.splitlines())


def test_command_refuses_a_directory_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths`: another exit code than 0, and no result."""
    shutil.copy(os.path.join(cell.ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(cell.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELLS[0],
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "the program is not in this checkout" in p.stderr
    assert p.stdout.strip() == ""
