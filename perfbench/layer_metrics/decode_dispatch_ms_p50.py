"""Median `decode.step.dispatch` span over the window: the two `put`s
of the step's inputs and the jitted `decode_step` / `decode_scan` call
until it returns, which is the enqueue and not the device's work."""
from perfbench.harness import numbers

LAYER = "serving control plane"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    ms = [s["dur"] / 1e3 for s in run.spans
          if s["name"] == "decode.step.dispatch"]
    return numbers.median(ms)
