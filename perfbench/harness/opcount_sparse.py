"""Operations and bytes an attention that reads selected blocks NEEDS,
from the program's counts alone: the positions of the blocks its
selection picked (keys and values, a key/value group's head a
position) and the pooled block keys its indexer scored (one a
complete block of what the rows hold, a group and a layer). What a
program reads beyond that (every pooled key of the rung, a whole block
around each write, the selection's own sorting) is not counted, so a
share of the roofline computed from these is a share of the least
time, never above 100 % while the program reads at least what it
needs. A configuration's `opcount_sparse` section names the function
and its widths; `layer_metrics/attn_sparse_roofline_pct.py` feeds it
the counters.
"""


def selected_blocks(positions_read, positions_held, heads_a_group,
                    head_dim, index_heads, index_dim, block, itemsize,
                    blockkey_itemsize):
    """(ops, bytes) of attending `positions_read` positions (a sum over
    rows, layers and groups) with `heads_a_group` query heads each, and
    of scoring the pooled keys of the blocks that `positions_held`
    positions fill.

    Ops: a multiply-add a dimension for each head's score and for its
    weighted sum, 4 * Hg * D a position; a multiply-add a dimension of
    each indexer head's score, 2 * J * Di a pooled key.
    Bytes: a key and a value of D a position (`itemsize`), a pooled
    key of Di a block (`blockkey_itemsize`: the slab keeps them in
    the indexer's float32)."""
    keys = positions_held / block
    ops = (positions_read * 4 * heads_a_group * head_dim
           + keys * 2 * index_heads * index_dim)
    nbytes = (positions_read * 2 * head_dim * itemsize
              + keys * index_dim * blockkey_itemsize)
    return ops, nbytes
