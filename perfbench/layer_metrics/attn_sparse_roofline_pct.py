"""The block-sparse attention's share of its roofline in the traced
decode steps: the least time the chip could take to move what the
steps' selections NEEDED (the program's counts of positions read and
held, per step over the 30 s window, times the traced steps;
`harness/opcount_sparse.py` turns them into the keys and values of the
picked blocks and the pooled keys scored) over the device time of the
parts `msa_indexer` and `attn_sparse` in those steps
(`harness/moe_trace.py`). It reads the same work whatever implements
the attention: a program that moved every held position, or the whole
rung, for blocks it then masks shows it here.

`COUNTERS` names the step counters this reader needs (a model's
`step_counter_names`): a cell whose model counts them not is one the
metric's `workloads` leaves out."""
from perfbench.harness import cell, moe_trace, opcount
from perfbench.layer_metrics import attn_sparse_step_ms

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
COUNTERS = ("msa_positions_read", "msa_positions_held")


def read(run):
    red = moe_trace.of_run(run)
    d = run.counters.get("decode", {})
    read_, held = (d.get(name) for name in COUNTERS)
    spec = run.config.get("opcount_sparse")
    if red is None or spec is None or not d.get("decode_steps") or not read_:
        return None
    spent = sum(red["seconds"].get(part, 0.0)
                for part in attn_sparse_step_ms.PARTS)
    if not spent:
        return None
    count = getattr(cell.module("harness", spec["module"]), spec["function"])
    per_step = red["steps"] / d["decode_steps"]
    ops, nbytes = count(read_ * per_step, held * per_step, **spec["kwargs"])
    least, bound = opcount.roofline_seconds(ops, nbytes, run.peaks)
    run.notes["attn_sparse_roofline"] = (
        f"{bound}-bound; {red['steps']} steps need {least:.4f} s at the "
        f"peak ({read_ / d['decode_steps']:.0f} positions read a step of "
        f"the {held / d['decode_steps']:.0f} held: "
        f"{100.0 * read_ / held:.1f} %), the operations took {spent:.4f} s")
    return 100.0 * least / spent
