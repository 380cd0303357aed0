"""`device_idle_pct` for the serving cells, where what it moves is the
tokens delivered and not the items trained."""
from perfbench.layer_metrics.device_idle_pct import read  # noqa: F401

LAYER = "device"
UNIT = "%"
MOVES = "out_tokens_per_s"
