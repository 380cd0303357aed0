"""Operations and bytes a routed expert layer NEEDS, from shapes and
assignment counts alone (a multiply-add is 2 operations). What a kernel
does beyond the need (every held expert over every row of a decode
step) is not counted, so a share of the roofline computed from these
is a share of the least time, never above 100 %. A configuration's
`opcount` section names the function and its widths;
`layer_metrics/moe_experts_roofline_pct.py` feeds it the counters.
"""


def expert_products(assignments, experts_touched, d_model, d_ff_expert,
                    itemsize):
    """(ops, bytes) of the gated expert MLPs for `assignments`
    (token, expert) pairs that fall on `experts_touched` distinct
    experts held here (both may be sums over layers and steps).

    Ops: gate, up and down products, 3 * 2 * d * f an assignment.
    Bytes: each touched expert's three matrices once, 3 * d * f, plus
    a row of d read and a row of d written an assignment."""
    ops = assignments * 3 * 2 * d_model * d_ff_expert
    nbytes = (experts_touched * 3 * d_model * d_ff_expert
              + assignments * 2 * d_model) * itemsize
    return ops, nbytes
