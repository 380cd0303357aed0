"""Executables jax built inside the measured window (compiled or
loaded from the cache). Must be 0; `correct` is false when it is not."""
LAYER = "placement and compile"
UNIT = "count"
MOVES = "setup_s"


def read(run):
    return run.compiles_in_window
