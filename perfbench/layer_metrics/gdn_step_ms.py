"""Device time a fused decode step spends in the Gated DeltaNet layers'
recurrence: the `gated_delta_step` kernel with the packing of its
inputs, the short convolution's update of the slot's state and the
gated norm (part `gdn` of the configuration's `step_parts`), over the
decode steps of the traced sub-window: operations found by the kernel's
name and the shapes in their instruction text inside the programs
`slot_step` / `slot_scan_<k>` (`harness/moe_trace.py`). The
projections are part `dense`."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "out_tokens_per_s"


def read(run):
    return moe_trace.step_ms(run, "gdn")
