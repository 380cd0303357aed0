"""Time in collective operations during which no other operation runs
on that chip, over the traced sub-window; mean over the chips."""
from perfbench.harness import xplane

LAYER = "device"
UNIT = "%"
MOVES = "train_items_per_s"


def read(run):
    if run.device_trace is None:
        return None
    return xplane.collective_exposed_pct(run.device_trace,
                                         *run.trace_window_ns)
