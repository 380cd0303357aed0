"""Device time a fused decode step of the Gated DeltaNet model spends in
its held experts' products (gate, up, down over the rows routed to them:
part `moe_experts` of its `step_parts`), over the decode steps of the
traced sub-window (`harness/moe_trace.py`): `moe_experts_step_ms`'s
quantity in this model's cell, beside `gdn_step_ms`,
`attn_gated_step_ms` and `gdn_dense_step_ms`."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "out_tokens_per_s"


def read(run):
    return moe_trace.step_ms(run, "moe_experts")
