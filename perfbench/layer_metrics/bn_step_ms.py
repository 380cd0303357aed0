"""Device ms a traced step under the BatchNorm layers' operator,
forward and backward (statistics, normalisation, and their
transposes)."""
from perfbench.harness import scope_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "train_items_per_s"
SCOPES = r"/_BatchNorm2d(?:/|$)"


def read(run):
    return scope_trace.step_ms(run, SCOPES)
