"""Device time a fused decode step spends in the dense products of a
model whose attention is timed apart (the fused q/k/v projection, the
output projection and the gated MLP's three: part `dense` of its
`step_parts`), over the decode steps of the traced sub-window
(`harness/moe_trace.py`). With `attn_chunked_step_ms` and the head it
accounts for the device's step."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "out_tokens_per_s"


def read(run):
    return moe_trace.step_ms(run, "dense")
