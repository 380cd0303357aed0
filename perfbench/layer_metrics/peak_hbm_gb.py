"""Peak device memory over the process, on the fullest chip."""
import jax

LAYER = "device"
UNIT = "GB"
MOVES = "train_items_per_s"


def read(run):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p]
    return max(peaks) / 1e9 if peaks else None
