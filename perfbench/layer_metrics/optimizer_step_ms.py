"""Device ms a traced step under the optimizer's scopes: every
parameter's update (`opt/<class>/<parameter>`) and the glue around
them (`opt/guard`, `opt/clip`, `opt/accum`, ...)."""
from perfbench.harness import scope_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "train_items_per_s"
SCOPES = r"^opt/"


def read(run):
    return scope_trace.step_ms(run, SCOPES)
