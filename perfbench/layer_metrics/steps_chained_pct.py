"""Share of the window's decode steps that the dispatcher enqueued behind
a block whose tokens it had not read back yet, from the program's decode
counters: how often the next block ran on the device while the host read
back and handed out the one before it. 0 where a slot is free, a session
samples or carries a deadline, or a session ends at every block's end. A
program that does not count `decode_steps_chained` (one whose dispatcher
reads every block back before the next) leaves nothing to read."""
LAYER = "serving control plane"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.counters.get("decode")
    if not d or not d.get("decode_steps") or "decode_steps_chained" not in d:
        return None
    return 100.0 * d["decode_steps_chained"] / d["decode_steps"]
