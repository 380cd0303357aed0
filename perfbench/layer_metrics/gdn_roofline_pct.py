"""The gated delta rule's share of its roofline in the traced decode
steps: the least time the chip could take to move what the steps'
states NEEDED (the program's count of rows whose states it advanced,
per step over the 30 s window, times the traced steps;
`harness/opcount_gdn.py` turns it into each row's matrix states read
and written once and its q, k, v, g and beta) over the device time of
part `gdn` in those steps (`harness/moe_trace.py`). It reads the same
work whatever implements the recurrence: a program that moves a state
twice a step, or re-lays it out, shows it here.

`COUNTERS` names the step counters this reader needs (a model's
`step_counter_names`): a cell whose model counts them not is one the
metric's `workloads` leaves out."""
from perfbench.harness import cell, moe_trace, opcount

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
COUNTERS = ("gdn_state_rows",)


def read(run):
    red = moe_trace.of_run(run)
    d = run.counters.get("decode", {})
    rows = d.get(COUNTERS[0])
    spec = run.config.get("opcount_gdn")
    if red is None or spec is None or not d.get("decode_steps") or not rows:
        return None
    spent = red["seconds"].get("gdn", 0.0)
    if not spent:
        return None
    count = getattr(cell.module("harness", spec["module"]), spec["function"])
    per_step = red["steps"] / d["decode_steps"]
    ops, nbytes = count(rows * per_step, **spec["kwargs"])
    least, bound = opcount.roofline_seconds(ops, nbytes, run.peaks)
    run.notes["gdn_roofline"] = (
        f"{bound}-bound; {red['steps']} steps need {least:.4f} s at the "
        f"peak ({rows / d['decode_steps']:.0f} rows' states advanced a "
        f"step), part gdn took {spent:.4f} s")
    return 100.0 * least / spent
