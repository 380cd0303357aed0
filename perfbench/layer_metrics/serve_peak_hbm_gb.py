"""`peak_hbm_gb` for the serving cells: what decides how many slots
and how long a slab fit, and so the tokens delivered."""
from perfbench.layer_metrics.peak_hbm_gb import read  # noqa: F401

LAYER = "device"
UNIT = "GB"
MOVES = "out_tokens_per_s"
