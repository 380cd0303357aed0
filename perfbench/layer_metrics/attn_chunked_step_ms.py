"""Device time a fused decode step spends attending the window buffers
and the chunk summaries (the buffer write, the scores over both, the
one softmax, both weighted sums: part `attn_chunked`) and pooling and
writing the summaries of the chunks that close (part `chunk_summary`),
over the decode steps of the traced sub-window: operations found by the
shapes and kernel names in their instruction text inside the programs
`slot_step` / `slot_scan_<k>` (`harness/moe_trace.py`, the
configuration's `step_parts`)."""
from perfbench.harness import moe_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "out_tokens_per_s"
PARTS = ("attn_chunked", "chunk_summary")


def read(run):
    parts = [moe_trace.step_ms(run, part) for part in PARTS]
    if parts[0] is None:
        return None
    return sum(ms or 0.0 for ms in parts)
