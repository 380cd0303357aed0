"""A decode session's wait for a slot and for its turn, apart from its
prefill: the program's `decode_queue_wait` span, from `submit_decode`
to the pop into a prefill cohort (or a KV import), 90th percentile
over the window. In the closed-loop cell it is what `ttft_p90_ms` is
made of beside the prefill; one metric has one `moves` that all its
cells report, so it follows `client_ttft_p90_ms`."""
from perfbench.harness import numbers

LAYER = "serving control plane"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    ms = [s["dur"] / 1e3 for s in run.spans
          if s["name"] == "decode_queue_wait"]
    return numbers.percentile(ms, 90)
