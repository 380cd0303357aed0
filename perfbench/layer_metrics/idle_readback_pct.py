"""Share of the traced sub-window in which the chip ran no operation
and the decode dispatcher was in a `*.readback` span (`np.asarray` of
the step's or the prefill's output): the device had finished, the
result was not on the host yet. By intersection with the program's
spans on the device trace's clock (`harness/program_trace.py`)."""
from perfbench.harness import program_trace

LAYER = "device"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(run):
    return program_trace.idle_pct(run, "readback")
