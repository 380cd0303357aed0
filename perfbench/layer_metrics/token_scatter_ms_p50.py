"""Median `decode.step.scatter` span over the window: per-session
argmax or sampling over the step's output, token delivery, retiring
finished sessions, the metrics record."""
from perfbench.harness import numbers

LAYER = "serving control plane"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    ms = [s["dur"] / 1e3 for s in run.spans
          if s["name"] == "decode.step.scatter"]
    return numbers.median(ms)
