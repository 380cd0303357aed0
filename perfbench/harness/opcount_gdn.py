"""Operations and bytes a decode step's gated delta rule NEEDS, from
the program's count of the rows whose states it advanced: each row's
matrix state of every value head read once and written once, and the
row's q, k, v, g and beta read. What a program moves beyond that (a
state twice, a layout copy of it, the packing of its inputs) is not
counted, so a share of the roofline computed from these is a share of
the least time, never above 100 % while the program moves at least
what it needs. A configuration's `opcount_gdn` section names the
function and its widths; `layer_metrics/gdn_roofline_pct.py` feeds it
the counter.
"""


def state_steps(rows, value_heads, key_dim, value_dim, state_itemsize,
                io_itemsize):
    """(ops, bytes) of advancing `rows` rows' states by one position (a
    sum over rows, layers and steps), `value_heads` states of [key_dim,
    value_dim] a row.

    Ops: a state's decay, S^T k, the rank-1 update (a multiply and an
    add) and S^T q, 7 * Dk * Dv a head.
    Bytes: the state read and written (`state_itemsize`), and q and k
    of Dk, v of Dv, g and beta a head (`io_itemsize`)."""
    heads = rows * value_heads
    ops = heads * 7 * key_dim * value_dim
    nbytes = heads * (2 * key_dim * value_dim * state_itemsize
                      + (2 * key_dim + value_dim + 2) * io_itemsize)
    return ops, nbytes
