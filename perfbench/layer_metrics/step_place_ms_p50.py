"""Median of the program's `step.place` phase in the traced window:
the step's inputs put onto their layout (one chip: nothing to do; a
mesh: every batch re-placed from where the loop left it)."""
from perfbench.harness import scope_trace

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(run):
    return scope_trace.phase_ms_p50(run, scope_trace.PLACE)
