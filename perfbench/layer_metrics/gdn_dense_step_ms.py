"""Device time a fused decode step of the Gated DeltaNet model spends in
its dense products (the Gated DeltaNet and attention projections, the
gated shared expert and its gate: part `dense` of its `step_parts`), over
the decode steps of the traced sub-window (`harness/moe_trace.py`):
`dense_step_ms`'s quantity for the model whose recurrence is
`gdn_step_ms`, whose attention is `attn_gated_step_ms` and whose held
experts are `gdn_experts_step_ms`."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "out_tokens_per_s"


def read(run):
    return moe_trace.step_ms(run, "dense")
