"""Share of the traced sub-window in which the chip ran no operation
and the decode dispatcher was in `decode.wait_work`: nothing queued,
nothing live, no demand."""
from perfbench.harness import program_trace

LAYER = "device"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(run):
    return program_trace.idle_pct(run, "nowork")
