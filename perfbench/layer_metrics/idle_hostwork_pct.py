"""Share of the traced sub-window in which the chip ran no operation
and the decode dispatcher was doing its own work: `decode.admit`, a
`*.assemble` or a `*.scatter` span (expiry, admission, input vectors,
argmax or sampling, token delivery)."""
from perfbench.harness import program_trace

LAYER = "device"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(run):
    return program_trace.idle_pct(run, "hostwork")
