"""How long after the chip had finished the host held the result: per
`decode.step.readback` span in the traced sub-window, its end minus
the later of its own start and the end of the last device operation
that ended before it closed; median. Whatever the block length: a
run-ahead block pays it once, a single step every time."""
from perfbench.harness import program_trace

LAYER = "serving control plane"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    red = program_trace.of_run(run)
    return None if red is None else red["readback_tail_ms_p50"]
