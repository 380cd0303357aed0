"""Share of the traced sub-window in which no operation ran on the
device: 1 - union of the operations' intervals, mean over the chips."""
from perfbench.harness import xplane

LAYER = "device"
UNIT = "%"
MOVES = "train_items_per_s"


def read(run):
    if run.device_trace is None:
        return None
    return xplane.idle_pct(run.device_trace, *run.trace_window_ns)
