"""The chunked attention's share of its roofline in the traced decode
steps: the least time the chip could take to move what the steps' rows
NEEDED (the program's count of entries, exact keys of the query's own
block plus the summaries of the earlier blocks, per step over the 30 s
window, times the traced steps; `harness/opcount_chunked.py` turns
entries into bytes) over the device time of the parts `attn_chunked`
and `chunk_summary` in those steps (`harness/moe_trace.py`). It reads
the same work whatever implements the attention: a program that reads
the whole buffer and the whole summary list for a row that holds half
of one and a quarter of the other shows it here.

`COUNTERS` names the step counters this reader needs (a model's
`step_counter_names`): a cell whose model counts them not is one the
metric's `workloads` leaves out."""
from perfbench.harness import cell, moe_trace, opcount
from perfbench.layer_metrics import attn_chunked_step_ms

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
COUNTERS = ("attn_entries_needed", "attn_entries_held")


def read(run):
    red = moe_trace.of_run(run)
    d = run.counters.get("decode", {})
    needed, held = (d.get(name) for name in COUNTERS)
    if red is None or not d.get("decode_steps") or not needed:
        return None
    spent = sum(red["seconds"].get(part, 0.0)
                for part in attn_chunked_step_ms.PARTS)
    if not spent:
        return None
    spec = run.config["opcount"]
    count = getattr(cell.module("harness", spec["module"]), spec["function"])
    ops, nbytes = count(needed * red["steps"] / d["decode_steps"],
                        **spec["kwargs"])
    least, bound = opcount.roofline_seconds(ops, nbytes, run.peaks)
    run.notes["attn_chunked_roofline"] = (
        f"{bound}-bound; {red['steps']} steps need {least:.4f} s at the "
        f"peak ({needed / d['decode_steps']:.0f} entries a step of the "
        f"{held / d['decode_steps']:.0f} held: "
        f"{100.0 * needed / held:.1f} %), the operations took "
        f"{spent:.4f} s")
    return 100.0 * least / spent
