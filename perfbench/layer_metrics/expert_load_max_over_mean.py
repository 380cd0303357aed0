"""How unevenly a decode step's rows load the held experts: the
fullest held expert's assignments over the mean a held expert, summed
over routed layers and the window's decode steps, from the program's
counters. 1 is even; the step waits for the fullest."""
LAYER = "model math"
UNIT = "ratio"
MOVES = "tpot_p50_ms"


def read(run):
    d = run.counters.get("decode", {})
    if not d.get("moe_assignments_local"):
        return None
    held = run.config["builder"]["kwargs"]["held"][1]
    # both are sums over the same layer-steps: the mean a held expert
    # is the assignments over `held` of them
    return d["moe_expert_load_max"] / (d["moe_assignments_local"] / held)
