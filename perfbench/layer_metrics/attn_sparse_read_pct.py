"""Share of what the rows hold that the decode steps' block-sparse
attention read, over the window: the positions of the blocks its
indexer picked (block 0, the local ones and the best-scoring, whole
blocks of 128) over the positions 0 .. pos the rows hold, each summed
over rows, layers, key/value groups and steps, from the program's
counters. 100 is every held position; a query before its 19th block
reads them all (the own block whole, so a little over 100 there).

`COUNTERS` names the step counters this reader divides (a model's
`step_counter_names`): a cell whose model counts them not is one the
metric's `workloads` leaves out."""
LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
COUNTERS = ("msa_positions_read", "msa_positions_held")


def read(run):
    d = run.counters.get("decode", {})
    read_, held = (d.get(name) for name in COUNTERS)
    if not held:
        return None
    return 100.0 * read_ / held
