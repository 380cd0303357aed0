"""Median time `model(x, y)` takes to return, on the benchmark's own
clock around the call. The program's `dispatch` span measures the same
thing, but switching its tracer on adds a device fence to every step
(`model.py`, `device_sync`), which would change what is measured."""
from perfbench.harness import numbers

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(run):
    d = run.samples.get("host_dispatch_s")
    return 1e3 * numbers.median(d) if d else None
