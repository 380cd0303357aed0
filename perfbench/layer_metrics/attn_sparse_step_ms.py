"""Device time a fused decode step spends in the learned block-sparse
attention: the indexer's scores over the pooled block keys, its top-k
and the pooled keys' running max (part `msa_indexer`), and the write of
the step's key and value with the attention over the selected blocks
(part `attn_sparse`: `cache_write` and `selected_blocks_attend`), over
the decode steps of the traced sub-window: operations found by the
shapes and kernel names in their instruction text inside the programs
`slot_step` / `slot_scan_<k>` (`harness/moe_trace.py`, the
configuration's `step_parts`). The projections are part `dense`."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "out_tokens_per_s"
PARTS = ("msa_indexer", "attn_sparse")


def read(run):
    parts = [moe_trace.step_ms(run, part) for part in PARTS]
    if parts[1] is None:
        return None
    return sum(ms or 0.0 for ms in parts)
