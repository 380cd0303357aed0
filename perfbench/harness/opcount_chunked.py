"""Bytes an attention over a window buffer and chunk summaries NEEDS,
from the program's count of entries alone: an entry is one position's
(or one chunk's pooled) key and value over every head. What a program
reads beyond the need (the whole buffer and the whole summary list
whatever the rows hold, a block of 128 positions around each write) is
not counted, so a share of the roofline computed from these is a share
of the least time, never above 100 % while the program reads at least
what it needs. A configuration's `opcount` section names the function
and its widths; `layer_metrics/attn_chunked_roofline_pct.py` feeds it
the counter.
"""


def entries_needed_bytes(entries, num_heads, head_dim, itemsize):
    """(ops, bytes) of attending `entries` keys and values (a sum over
    rows, layers and steps): a multiply-add a dimension for the score
    and one for the weighted sum, 2 * 2 * H * D operations an entry;
    a key and a value read once, 2 * H * D * itemsize bytes."""
    return (entries * 4 * num_heads * head_dim,
            entries * 2 * num_heads * head_dim * itemsize)
