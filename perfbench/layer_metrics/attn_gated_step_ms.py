"""Device time a fused decode step of the Gated DeltaNet model spends in
its gated full-attention layers' attention over the cache (the write of
the step's key and value into the context, the scores and the weighted
values; projections left out: part `attn_full` of its `step_parts`), over
the decode steps of the traced sub-window (`harness/moe_trace.py`):
`attn_full_step_ms`'s quantity in a cell that reports throughput and not
`tpot_p50_ms`."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "out_tokens_per_s"


def read(run):
    return moe_trace.step_ms(run, "attn_full")
