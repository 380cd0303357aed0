"""Device time a fused decode step spends in the gated short-convolution
operators (the input projection into b, c, x, the taps over the slot's
state and the new u, the state moved on by one), over the decode steps
of the traced sub-window: operations found by the shapes in their
instruction text inside the programs `slot_step` / `slot_scan_<k>`
(`harness/moe_trace.py`, the configuration's `step_parts`)."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    return moe_trace.step_ms(run, "short_conv")
