"""Per-request time per output token at the client, 90th percentile:
the tail of what `tpot_p50_ms` takes the median of. Over a few hundred
requests it spreads by 3 to 5 % between runs of the same code (PR 22),
too wide for a bound, so it is read here."""
from perfbench.harness import numbers

LAYER = "entry"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    s = run.samples.get("tpot_s")
    return 1e3 * numbers.percentile(s, 90) if s else None
