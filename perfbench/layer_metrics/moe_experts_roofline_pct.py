"""The expert products' share of their roofline in the traced decode
steps: the least time the chip could take for what the steps' routing
needed (`harness/opcount_moe.py` fed by the program's counters of
local assignments and held experts touched, per step over the 30 s
window, times the traced steps) over the device time of the expert
products' operations in those steps (`harness/moe_trace.py`). At a few
rows an expert the need is the touched experts' weights once, so the
bound that binds is memory."""
from perfbench.harness import moe_trace, opcount, opcount_moe

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(run):
    red = moe_trace.of_run(run)
    d = run.counters.get("decode", {})
    if (red is None or not red["seconds"]["moe_experts"]
            or not d.get("decode_steps") or not d.get("moe_experts_touched")):
        return None
    spec = run.config["opcount"]
    per_step = red["steps"] / d["decode_steps"]
    ops, nbytes = getattr(opcount_moe, spec["function"])(
        d["moe_assignments_local"] * per_step,
        d["moe_experts_touched"] * per_step, **spec["kwargs"])
    least, bound = opcount.roofline_seconds(ops, nbytes, run.peaks)
    spent = red["seconds"]["moe_experts"]
    run.notes["moe_experts_roofline"] = (
        f"{bound}-bound; {red['steps']} steps need {least:.4f} s at the "
        f"peak ({d['moe_assignments_local'] / d['decode_steps']:.1f} local "
        f"assignments on {d['moe_experts_touched'] / d['decode_steps']:.1f} "
        f"expert-layers a step), the operations took {spent:.4f} s")
    return 100.0 * least / spent
