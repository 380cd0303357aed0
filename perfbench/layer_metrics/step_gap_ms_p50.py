"""The dispatcher thread's own work between dispatches: from the end
of one `prefill` / `decode_step` span to the start of the next, median
over the window."""
from perfbench.harness import numbers

LAYER = "serving control plane"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    spans = sorted((s["ts"], s["ts"] + s["dur"]) for s in run.spans
                   if s["name"] in ("prefill", "decode_step"))
    gaps = [(b[0] - a[1]) / 1e3 for a, b in zip(spans, spans[1:])]
    return numbers.median(gaps) if gaps else None
