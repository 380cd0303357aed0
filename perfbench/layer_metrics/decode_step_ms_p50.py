"""Median time of one fused decode step: the program's `decode_step`
span over the steps in its block (a run-ahead block is one span)."""
from perfbench.harness import numbers

LAYER = "model math"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    steps = [s["dur"] / 1e3 / s["args"]["steps"] for s in run.spans
             if s["name"] == "decode_step"]
    return numbers.median(steps) if steps else None
