"""How late the load generator ran: actual send minus due (open loop),
or submit minus the engine's delivery of the client's previous reply
(closed loop). A starved generator is not a fast server."""
from perfbench.harness import numbers

LAYER = "entry"
UNIT = "ms"
MOVES = "tpot_p50_ms"


def read(run):
    late = run.samples.get("gen_late_s")
    return 1e3 * numbers.percentile(late, 99) if late else None
