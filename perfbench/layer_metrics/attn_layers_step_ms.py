"""Device ms a traced step under the attention layers (`<block>.attn`
and the four projections below it), forward and backward: the
projections, the head split and merge, and the three flash calls."""
from perfbench.harness import scope_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "train_items_per_s"
# a layer's scope, not the update of its parameters (`opt/…attn.q_proj.W`)
SCOPES = r"^(?!opt/).*\.attn[./]"


def read(run):
    return scope_trace.step_ms(run, SCOPES)
