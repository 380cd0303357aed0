"""Share of the traced sub-window in which the chip ran no operation
and the decode dispatcher was in a `*.dispatch` span (the inputs'
`put`s and the jitted call until it returns): the enqueue had not
reached the chip yet."""
from perfbench.harness import program_trace

LAYER = "device"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(run):
    return program_trace.idle_pct(run, "dispatch")
