"""Share of the traced window's device busy time that falls under a
scope the program entered: a layer's or the loss's operator (forward
or backward), or the optimizer's update. The rest is named by opcode
in the run's `step_scopes` line."""
from perfbench.harness import scope_trace

LAYER = "model math"
UNIT = "%"
MOVES = "train_items_per_s"


def read(run):
    got = scope_trace.of_run(run)
    if got is None or got["scopes"] is None:
        return None
    return scope_trace.scoped_pct(got["scopes"])
