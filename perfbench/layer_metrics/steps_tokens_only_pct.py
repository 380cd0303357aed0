"""Share of the window's decode steps whose result came to the host as
tokens (`[k, slots]` int32: a greedy single step's token program and a
block's steps alike) and not as logits, from the program's decode
counters. 100 while every live session is greedy; a step with a
sampled session brings its logits back and counts against it. A
program that does not count `decode_steps_tokens` (before PR 33)
leaves nothing to read."""
LAYER = "serving control plane"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.counters.get("decode")
    if not d or not d.get("decode_steps") or "decode_steps_tokens" not in d:
        return None
    return 100.0 * d["decode_steps_tokens"] / d["decode_steps"]
