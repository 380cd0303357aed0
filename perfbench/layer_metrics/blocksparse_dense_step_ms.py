"""Device time a fused decode step of the block-sparse model spends in
its dense products (every projection, the indexer's included, the first
layer's MLP and the shared expert: part `dense` of its `step_parts`), over the
decode steps of the traced sub-window (`harness/moe_trace.py`):
`dense_step_ms`'s quantity for the model whose attention is
`attn_sparse_step_ms` and whose experts are `moe_experts_step_ms`."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "out_tokens_per_s"


def read(run):
    return moe_trace.step_ms(run, "dense")
