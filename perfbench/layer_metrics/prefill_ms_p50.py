"""Median time of one prefill cohort dispatch: the program's `prefill`
span."""
from perfbench.harness import numbers

LAYER = "model math"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    d = [s["dur"] / 1e3 for s in run.spans if s["name"] == "prefill"]
    return numbers.median(d) if d else None
