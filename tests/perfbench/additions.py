"""What later PRs add to the benchmark, as the `grown` root of
`conftest.py` carries it: NEW files and APPENDED entries alone, three
additions at once.

(a) a configuration (the toy of `toy_moe/`, given a fourth `step_parts`
    part found by an operation's name and tried first), its cell joined
    to every list the hybrid model's cell is on, a device-trace metric
    of its own, and a metric for the GPT-2 serving cells alone whose
    reader names its `COUNTERS`;
(b) a SECOND cell on a configuration the benchmark has, `lfm2-24b-a2b`
    (its traffic file under a new name), appended after (a)'s and
    joined to every list the lfm2 cell is on, `conv_step_ms` included;
(c) so that two cells and two per-layer entries follow everything this
    repository has.

A test that pins today's census (a count of cells, the name of the
last, a list held equal to today's, a table of today's exceptions)
fails on this root in the PR that writes it, where its author can
still change it, and not in the PR after."""
import copy
import json
import os
import shutil

HERE = os.path.dirname(__file__)
TOY_MOE = os.path.join(HERE, "toy_moe", "perfbench")
NEW_CONFIG, NEW_CELL = "rehearsed", "rehearsed-serve"
NEW_METRIC = "rehearsed_kernel_step_ms"
JOINS = "mimo-v2.5-serve-mixedlen"      # the cell whose lists (a)'s joins
AGAIN_OF = "lfm2-24b-a2b-serve-decode128"   # the cell (b)'s is a second of
AGAIN_CELL = "lfm2-24b-a2b-serve-rehearsed"
READER = '''"""Device time a fused decode step spends in the attention kernel."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    return moe_trace.step_ms(run, "attn_kernel")
'''
# a metric for the GPT-2 serving cells alone, as PR 30 wanted one: its
# own file says which step counters it reads, and the two drawn models
# count none of them, so their cells are left off its list
GPT2_ONLY = ["gpt2-serve-decode", "gpt2-serve-short"]
GPT2_METRIC = "rehearsed_blocks_read_per_step"
GPT2_READER = '''"""Blocks the decode steps' attention read, a step."""
LAYER = "kernels"
UNIT = "blocks"
MOVES = "out_tokens_per_s"
COUNTERS = ("attn_blocks_read",)


def read(run):
    d = run.counters.get("decode", {})
    if not d.get("decode_steps") or not d.get(COUNTERS[0]):
        return None
    return d[COUNTERS[0]] / d["decode_steps"]
'''
NEW_ENTRY = {"name": NEW_METRIC, "unit": "ms", "better": "lower",
             "source": "device_trace", "layer": "model math",
             "moves": "tpot_p50_ms", "workloads": [NEW_CELL]}
GPT2_ENTRY = {"name": GPT2_METRIC, "unit": "blocks", "better": "lower",
              "source": "program_counter", "layer": "kernels",
              "moves": "out_tokens_per_s", "workloads": GPT2_ONLY}


def new_config():
    with open(os.path.join(TOY_MOE, "configs", "toy_moe.json")) as f:
        config = json.load(f)
    config["step_parts"] = {"attn_kernel": ["decode_attend"],
                            **config["step_parts"]}
    return config


def joined(m):
    """The entry `m` of this repository's BENCHMARK.json as the grown
    root has it: each new cell at the end of the lists the cell it
    follows is on, (a)'s before (b)'s as `workloads` has them."""
    if "workloads" not in m:
        return m
    return {**m, "workloads": m["workloads"]
            + [NEW_CELL] * (JOINS in m["workloads"])
            + [AGAIN_CELL] * (AGAIN_OF in m["workloads"])}


def grow(bench):
    """`bench` with the three additions' entries appended; `bench` is
    left as it was."""
    bench = copy.deepcopy(bench)
    (again,) = [w for w in bench["workloads"] if w["name"] == AGAIN_OF]
    bench["configs"].append({
        "name": NEW_CONFIG, "source": new_config()["source"],
        "file": f"perfbench/configs/{NEW_CONFIG}.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"] += [
        {"name": NEW_CELL, "config": NEW_CONFIG, "traffic": "serve",
         "chips": 1, "why": "rehearsal"},
        {"name": AGAIN_CELL, "config": again["config"],
         "traffic": "serve-rehearsed", "chips": 1, "why": "rehearsal"}]
    for group in ("end_to_end", "per_layer"):
        bench[group] = [joined(m) for m in bench[group]]
    bench["per_layer"] += [copy.deepcopy(NEW_ENTRY),
                           copy.deepcopy(GPT2_ENTRY)]
    return bench


def _files(root):
    return {os.path.relpath(os.path.join(d, f), root)
            for d, _, files in os.walk(root) for f in files}


def build(ours, root):
    """Copy `ours`' BENCHMARK.json and perfbench/ into the directory
    `root` and add the files and entries; returns `root` as a string."""
    root = str(root)
    shutil.copy(os.path.join(ours, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ours, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = grow(json.load(f))
    with open(os.path.join(root, "perfbench", "configs",
                           NEW_CONFIG + ".json"), "w") as f:
        json.dump(new_config(), f)
    workloads = os.path.join(root, "perfbench", "workloads")
    shutil.copy(os.path.join(TOY_MOE, "workloads", "toy-moe-serve.json"),
                os.path.join(workloads, NEW_CELL + ".json"))
    shutil.copy(os.path.join(workloads, AGAIN_OF + ".json"),
                os.path.join(workloads, AGAIN_CELL + ".json"))
    for name, text in ((NEW_METRIC, READER), (GPT2_METRIC, GPT2_READER)):
        with open(os.path.join(root, "perfbench", "layer_metrics",
                               name + ".py"), "w") as f:
            f.write(text)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    after = _files(root)
    assert before <= after and len(after - before) == 5
    return root
