"""Median of the program's own `step.call` phase (all of the compiled
step's `__call__`) in the traced window, on the trace's clock: the
inside twin of `host_dispatch_ms_p50`, which times the same call from
outside on the benchmark's clock."""
from perfbench.harness import scope_trace

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(run):
    return scope_trace.phase_ms_p50(run, scope_trace.CALL)
