"""Share of the traced sub-window in which the chip ran no operation
and the decode dispatcher was in none of its leaf spans. With the four
other `idle_*_pct` it adds up to `serve_device_idle_pct`; more than a
point or two means a phase of the cycle has no span."""
from perfbench.harness import program_trace

LAYER = "device"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(run):
    return program_trace.idle_pct(run, "unattributed")
