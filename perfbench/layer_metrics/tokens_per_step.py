"""Mean live sessions per fused decode step over the window: tokens
streamed over decode steps, from the program's decode counters."""
LAYER = "serving control plane"
UNIT = "tokens"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.counters.get("decode")
    if not d or not d.get("decode_steps"):
        return None
    return d["tokens_streamed"] / d["decode_steps"]
