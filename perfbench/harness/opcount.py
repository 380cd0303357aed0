"""Operations and bytes an algorithm needs, from shapes alone.

What the forward and backward passes REQUIRE: recomputation (the flash
backward's second pass over the scores, remat) is not counted, so a
share of peak computed from these is a model utilization, not a count
of what the chip executed. A multiply-add is 2 operations.
"""


def transformer_train_flops_per_token(vocab, d_model, layers, d_ff, seq):
    """Decoder-only LM, one token of a `seq`-long causal sequence,
    forward + backward (backward = 2x forward for every matmul).

    Per layer: q,k,v,o projections 4*d^2 weights, MLP 2*d*d_ff; causal
    attention scores + values average seq/2 keys per query:
    2 matmuls * 2 ops * (seq/2) * d. Head: d*vocab (tied or not, the
    matmul is done). Embedding lookups and norms are not matmuls."""
    per_layer = 2 * (4 * d_model * d_model + 2 * d_model * d_ff) \
        + 2 * 2 * (seq / 2) * d_model
    fwd = layers * per_layer + 2 * d_model * vocab
    return 3.0 * fwd


# ResNet (He et al., arXiv:1512.03385, Table 1): stage widths and
# block counts; bottleneck = 1x1 (planes), 3x3 (planes), 1x1 (4*planes).
_RESNET_BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def resnet_forward_macs(depth=50, image=224, classes=1000):
    """Multiply-adds of one image's forward pass through the
    convolutions and the classifier (BN, ReLU, pooling not counted).
    ResNet-50 at 224: 4.09e9 (the paper's Table 1 says 3.8e9 "FLOPs",
    counting multiply-adds, with the projection shortcuts left out)."""
    macs = 0
    hw = image // 2                      # conv1: 7x7/2, 3 -> 64
    macs += hw * hw * 7 * 7 * 3 * 64
    hw //= 2                             # maxpool /2
    inplanes = 64
    for stage, blocks in enumerate(_RESNET_BLOCKS[depth]):
        planes = 64 << stage
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            macs += hw * hw * inplanes * planes            # 1x1 (in res)
            out_hw = hw // stride
            macs += out_hw * out_hw * 9 * planes * planes  # 3x3, strided
            macs += out_hw * out_hw * planes * 4 * planes  # 1x1
            if b == 0:                                     # projection
                macs += out_hw * out_hw * inplanes * 4 * planes
            inplanes, hw = 4 * planes, out_hw
    return macs + inplanes * classes


def resnet_train_flops_per_image(depth=50, image=224, classes=1000):
    return 3.0 * 2.0 * resnet_forward_macs(depth, image, classes)


def attention_fwd_bwd(batch, heads, seq, head_dim, itemsize, causal=True):
    """(ops, bytes) one fused causal attention needs, forward plus
    backward, over [batch, heads, seq, head_dim].

    Ops: forward QK^T and PV, backward dP, dV, dQ, dK: 6 matmuls of
    2*seq*seq*head_dim each, halved by the causal mask. Bytes: forward
    reads q,k,v and writes o; backward reads q,k,v,o,do and writes
    dq,dk,dv: 12 tensors of seq*head_dim (the row statistics are
    seq*4 B and are left out)."""
    pairs = seq * seq / 2 if causal else seq * seq
    ops = batch * heads * 6 * 2 * pairs * head_dim
    nbytes = batch * heads * 12 * seq * head_dim * itemsize
    return ops, nbytes


def roofline_seconds(ops, nbytes, peaks, ops_key="bf16_flops_per_s"):
    """The least time the chip could take, and which bound binds."""
    t_ops = ops / peaks[ops_key]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
