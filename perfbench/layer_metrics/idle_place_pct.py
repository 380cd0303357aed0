"""Share of the traced window in which a chip runs nothing while the
host is inside the program's `step.place` phase; mean over the chips,
by intersection (as `program_trace` cuts a serving cell's idle)."""
from perfbench.harness import scope_trace

LAYER = "device"
UNIT = "%"
MOVES = "train_items_per_s"


def read(run):
    got = scope_trace.of_run(run)
    if got is None or not any(s[0] == scope_trace.PLACE
                              for s in got["spans"]):
        return None
    return scope_trace.idle_under_pct(got["devices"], got["spans"],
                                      scope_trace.PLACE,
                                      *run.trace_window_ns)
