"""Start and stop the device trace of a traced run's sub-window, and
reduce it. The trace lives at a fixed path inside the checkout."""
import os
import shutil

from . import cell as cell_mod
from . import xplane


def trace_dir(cell_name, root=cell_mod.ROOT):
    return os.path.join(root, ".perfbench_trace", cell_name)


class DeviceTrace:
    """`with DeviceTrace(run): ...` traces the block (the benchmark's
    annotation "bench:window" spans it) and leaves the reduced trace on
    `run.device_trace`."""

    def __init__(self, run):
        self.run = run
        self.dir = trace_dir(run.cell["name"])

    def __enter__(self):
        import jax.profiler

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self._win = jax.profiler.TraceAnnotation("bench:window")
        self._win.__enter__()
        return self

    def __exit__(self, *exc):
        import jax.profiler

        self._win.__exit__(*exc)
        jax.profiler.stop_trace()
        if exc[0] is None:
            path = xplane.newest_xplane(self.dir)
            tr = xplane.load(path)
            if not any(tr.devices.values()):
                raise RuntimeError(
                    "the profiler's trace holds no device operation: "
                    + xplane.describe(path))
            self.run.device_trace = tr
            self.run.trace_window_ns = xplane.window(tr)
        return False
