"""Device time a fused decode step spends in the held experts' products
(gate, up, down over the rows routed to them), over the decode steps of
the traced sub-window (`harness/moe_trace.py`, part `moe_experts` of
the configuration's `step_parts`): `moe_step_ms`'s quantity in a cell
that reports throughput and not `tpot_p50_ms`."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "out_tokens_per_s"


def read(run):
    return moe_trace.step_ms(run, "moe_experts")
