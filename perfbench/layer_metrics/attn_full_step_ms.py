"""Device time a fused decode step spends in the full-context layers' attention over the cache (the context's keys and values, scores and weighted values; projections left out), over the decode
steps of the traced sub-window: operations found by the shapes in
their instruction text inside the programs `slot_step` /
`slot_scan_<k>` (`harness/moe_trace.py`)."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    return moe_trace.step_ms(run, "attn_full")
