"""`steps_chained_pct`: the share of a window's decode steps that the
dispatcher enqueued behind a block it had not read back yet, read from
the program's decode counters, and its entry in BENCHMARK.json."""
import pytest

from perfbench.harness import cell as cell_mod

NAME = "steps_chained_pct"


def _run(root, counters):
    cell, config, workload = cell_mod.load_cell("gpt2-serve-decode", root)
    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=1.0, trace=True)
    if counters is not None:
        run.counters["decode"] = counters
    return run


@pytest.mark.parametrize("counters, want", [
    (None, None),                                       # no decode tier
    ({"decode_steps": 971, "decode_steps_tokens": 971}, None),  # no counter
    ({"decode_steps": 0, "decode_steps_chained": 0}, None),     # no step
    ({"decode_steps": 971, "decode_steps_chained": 0}, 0.0),
    ({"decode_steps": 971, "decode_steps_chained": 600}, 100 * 600 / 971),
    ({"decode_steps": 971, "decode_steps_chained": 971}, 100.0),
], ids=["no-decode", "parent", "no-step", "none-chained", "some-chained",
        "all-chained"])
def test_the_share_is_the_chained_steps_over_the_steps(root, counters, want):
    """A program that counts no chained steps, as one that never chains
    does not, leaves nothing to read; so does a window with no step."""
    reader = cell_mod.module("layer_metrics", NAME)
    got = reader.read(_run(root, counters))
    assert got == (want if want is None else pytest.approx(want))
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        "serving control plane", "%", "out_tokens_per_s")


def test_the_metric_is_registered_beside_the_tokens_only_share(root):
    """A counter of the serving control plane that moves
    `out_tokens_per_s`, on every cell the share of steps that came back
    as tokens is on (every serving cell), in the same order; its entry
    says what its reader says."""
    bench = cell_mod.benchmark(root)
    by_name = {m["name"]: dict(m) for m in bench["per_layer"]}
    reader = cell_mod.module("layer_metrics", NAME)
    entry = by_name[NAME]
    listed = entry.pop("workloads")
    assert entry == {"name": NAME, "unit": reader.UNIT, "better": "higher",
                     "source": "program_counter", "layer": reader.LAYER,
                     "moves": reader.MOVES}
    assert listed == by_name["steps_tokens_only_pct"]["workloads"]
    assert {"gpt2-serve-decode", "gpt2-serve-short"} <= set(listed)
    for name in listed:
        reported = {m["name"] for m in
                    cell_mod.metrics_for(name, "end_to_end", root)}
        assert reader.MOVES in reported
