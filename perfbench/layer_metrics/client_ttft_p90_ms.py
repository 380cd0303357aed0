"""Time to first token at the client, 90th percentile, where it is too
unsteady to carry a bound: in the open-loop cell the 90th percentile
sits on the edge between requests admitted in the cycle they arrived
in and those that waited out a second one (two admissions a cycle), so
it swings by a fifth between runs of the same code (PR 22). Same
samples and arithmetic as the end-to-end `ttft_p90_ms`."""
from perfbench.harness import numbers

LAYER = "entry"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    s = run.samples.get("ttft_s")
    return 1e3 * numbers.percentile(s, 90) if s else None
