"""Hand-written Pallas TPU kernels for the fused/odd ops.

Reference parity: `src/core/tensor/math_kernel.cu` (SURVEY.md N10) —
the reference's hand-written CUDA kernels for ops that don't decompose
well into library calls. SURVEY §7 plans exactly this tier for TPU:
"hand-written Pallas kernels for the fused/odd ones (softmax-xent,
dropout, top-K sparsification) registered as custom-calls". These are
those kernels:

  * `softmax_xent` — fused log-softmax + NLL with a custom-VJP Pallas
    backward (KernelSoftmaxCrossEntropy / KernelSoftmaxCrossEntropyBwd
    equivalents). One HBM round-trip for the whole loss instead of
    separate softmax / gather / reduce programs; the backward
    recomputes probs in-VMEM (no softmax residual in HBM). Logits,
    residual and gradient cross HBM in the logits' own dtype
    (bfloat16 under AMP); the arithmetic is float32 in VMEM.
  * `dropout` — mask generation with the TPU's on-core PRNG
    (pltpu.prng_random_bits) fused with the scale-and-mask multiply
    (KernelDropout equivalent).
  * `topk_threshold` + `threshold_mask` — top-K gradient
    sparsification (the reference's `sparsification(topK=true)`,
    src/io/communicator.cc): a block-accumulated |g| histogram kernel
    picks a conservative threshold (keeps >= K elements; exact K
    requires a global sort), and a mask kernel zeroes the rest.

Enablement: `enable(True)` or SINGA_TPU_PALLAS=1 — consumers
(`autograd.SoftMaxCrossEntropy`, `dist.Communicator.sparsification`)
check `enabled()`. On the CPU backend (the test suite) the kernels run
in Pallas interpret mode; on the TPU they compile to Mosaic, and
nothing there can reach the interpreter (`_interpret`).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ENABLED = os.environ.get("SINGA_TPU_PALLAS", "0") == "1"

# Per-kernel policy (VERDICT r4 next #3: "make every Pallas kernel pay
# or cut it").  The default tier routes ONLY the fused softmax-xent
# and flash attention, the latter from seq >= ~1024 (`attn_supported`).
# What says the xent kernels pay is the trace of the one cell that runs
# them, `gpt2-train-seq1024` (PERF.md section 5): over bfloat16
# [8192, 50257] logits they move them once a pass in that dtype, at
# the rate the HBM gives a streaming kernel, where the cast-first
# operator before PR 38 also paid a float32 copy of the logits and a
# convert of the d-logits back.  (benchmarks/PALLAS_BENCH.md's
# 1.07-1.80x for this kernel and 1.14-1.27x for flash attention were
# measured on float32 operands before PR 1, on another installation,
# and stand on no ledger line; ROADMAP A2 re-measures attention.)  The
# on-core-PRNG dropout (0.94x there) and the histogram top-K
# sparsifier (0.89-1.03x) sat at parity with XLA's own fusion — they
# remain correct, tested, and available, but engage only with
# SINGA_TPU_PALLAS_ALL=1 (or `enable_all`) so the default tier never
# trades a measured win for a measured loss.
_ALL = os.environ.get("SINGA_TPU_PALLAS_ALL", "0") == "1"
# ALL implies the tier itself: opting into the parity kernels with
# only SINGA_TPU_PALLAS_ALL=1 must not be a silent no-op.
_ENABLED = _ENABLED or _ALL
# Tuning knobs (exercised by benchmarks/pallas_tune.py on the chip):
_ATTN_MIN_SEQ = int(os.environ.get("SINGA_TPU_ATTN_MIN_SEQ", "1024"))


def enable(flag: bool = True) -> None:
    """Switch the Pallas kernel tier on/off (SINGA_TPU_PALLAS env also
    works)."""
    global _ENABLED
    _ENABLED = bool(flag)


def enabled() -> bool:
    return _ENABLED


def enable_all(flag: bool = True) -> None:
    """Also route the parity-with-XLA kernels (dropout, top-K
    sparsify) through Pallas — off by default; see the policy note.
    Enabling ALL enables the tier itself (never a silent no-op);
    disabling ALL leaves the tier's own switch untouched."""
    global _ALL, _ENABLED
    _ALL = bool(flag)
    if _ALL:
        _ENABLED = True


def dropout_enabled() -> bool:
    return _ENABLED and _ALL


def sparsify_enabled() -> bool:
    return _ENABLED and _ALL


def _interpret() -> bool:
    """Interpret mode on the CPU backend ONLY, so the test suite
    covers the kernel code paths; on the TPU the kernels compile to
    Mosaic, and any other backend fails at lowering rather than
    interpreting silently."""
    return jax.default_backend() == "cpu"


_ROW_BUDGET = int(os.environ.get("SINGA_TPU_ROW_BUDGET", str(1 << 19)))
_HIST_BUDGET = int(os.environ.get("SINGA_TPU_HIST_BUDGET", str(1 << 13)))


def _sublane(dtype) -> int:
    """Rows of a dtype's smallest tile: 8 of float32, 16 of a 2-byte
    dtype (two rows share a sublane), 32 of a 1-byte one."""
    return 32 // jnp.dtype(dtype).itemsize


def _row_tile(batch: int, ncol: int, budget: int = 0,
              dtype=jnp.float32) -> int:
    """Rows per block: keep a block under ~budget elements, a multiple
    of the dtype's sublane tile and never under one (a row wider than
    the budget still gets a block Mosaic can lay out), or the whole
    batch where that is smaller."""
    budget = budget or _ROW_BUDGET
    sub = _sublane(dtype)
    rows = max(sub, budget // max(ncol, 1))
    rows = min(batch, rows)
    if rows >= sub:
        rows -= rows % sub
    return max(rows, 1)


# ===========================================================================
# Fused softmax cross-entropy (forward + backward)
# ===========================================================================
def _xent_fwd_kernel(x_ref, lab_ref, loss_ref):
    x = x_ref[...].astype(jnp.float32)
    lab = lab_ref[...]  # (TILE_B, 1) int32
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    classes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x_lab = jnp.sum(jnp.where(classes == lab, x, 0.0), axis=-1,
                    keepdims=True)
    # Out-of-range labels (e.g. -1 padding) match the jnp path's
    # one_hot semantics: all-zero row -> zero loss contribution.
    valid = (lab >= 0) & (lab < x.shape[-1])
    loss_ref[...] = jnp.where(valid, jnp.log(s) + m - x_lab, 0.0)


def _xent_bwd_kernel(x_ref, lab_ref, g_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    lab = lab_ref[...]
    g = g_ref[...]  # (TILE_B, 1) upstream grad per row
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    classes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (classes == lab).astype(jnp.float32)
    # Same validity mask as the forward: padding rows (label -1 or
    # out-of-range) produced zero loss, so they get zero gradient.
    valid = (lab >= 0) & (lab < x.shape[-1])
    dx_ref[...] = jnp.where(valid, (p - onehot) * g,
                            0.0).astype(dx_ref.dtype)


def _pad_rows(a, tile):
    b = a.shape[0]
    pad = (-b) % tile
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a, b


_VMEM_DEFAULT = 16 << 20   # what Mosaic scopes a kernel to, unasked
# float32 arrays of a block's size the limit leaves room for: Mosaic
# holds two (compiled for a described v5e, the backward is refused
# with room for one); the third is to spare
_XENT_TEMPORARIES = 3


def _xent_call(kernel, name, logits, per_row, wide):
    """One of the two kernels over whole rows of `logits` [B, C], in
    the dtype they come in: a block is as many rows as the element
    budget gives in tiles of that dtype (at 50257 classes one tile: 8
    rows of float32, 16 of bfloat16), and float32 exists only in the
    kernel's VMEM. `per_row`: [B, 1] arrays; the result is `wide`
    ([B, C] in the logits' dtype) or a float32 [B, 1]. The limit asked
    of Mosaic is the block's own arithmetic: the logits' block and a
    wide result's, each twice (the pipeline's), and the float32
    temporaries; under 16 MiB, what a kernel gets unasked, it asks for
    that."""
    b, c = logits.shape
    tile = _row_tile(b, c, dtype=logits.dtype)
    sub = _sublane(logits.dtype)
    block = -(-tile // sub) * sub * -(-c // 128) * 128   # as VMEM pads it
    held = block * ((4 if wide else 2) * logits.dtype.itemsize
                    + 4 * _XENT_TEMPORARIES)
    row = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    whole = pl.BlockSpec((tile, c), lambda i: (i, 0))
    xp, _ = _pad_rows(logits, tile)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (xp.shape[0], c if wide else 1),
            logits.dtype if wide else jnp.float32),
        grid=(xp.shape[0] // tile,),
        in_specs=[whole] + [row] * len(per_row),
        out_specs=whole if wide else row,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(_VMEM_DEFAULT, held + (2 << 20))),
        interpret=_interpret(), name=name,
    )(xp, *(_pad_rows(a, tile)[0] for a in per_row))[:b]


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def softmax_xent(logits, labels):
    """Per-row cross-entropy loss, fused. logits (B, C) float,
    labels (B,) int -> (B,) float32. Mean/scale is the caller's. The
    logits cross HBM once a pass in the dtype they have; the gradient
    comes back in it."""
    loss, _ = _softmax_xent_fwd(logits, labels)
    return loss


def _softmax_xent_fwd(logits, labels):
    lab2 = labels.reshape(-1, 1).astype(jnp.int32)
    loss = _xent_call(_xent_fwd_kernel, "softmax_xent_fwd", logits,
                      (lab2,), wide=False)
    return loss[:, 0], (logits, labels)


def _softmax_xent_bwd(res, g):
    logits, labels = res
    lab2 = labels.reshape(-1, 1).astype(jnp.int32)
    g2 = g.reshape(-1, 1).astype(jnp.float32)
    dx = _xent_call(_xent_bwd_kernel, "softmax_xent_bwd", logits,
                    (lab2, g2), wide=True)
    return dx, None


softmax_xent.defvjp(_softmax_xent_fwd, _softmax_xent_bwd)


# ===========================================================================
# Fused dropout (TPU on-core PRNG + mask + scale in one pass)
# ===========================================================================
def _dropout_kernel(seed_ref, x_ref, out_ref, mask_ref, *, keep):
    pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    bits = pltpu.prng_random_bits(x_ref.shape)
    # uint32 -> uniform [0,1): take the top 24 bits.
    u = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    mask = (u < keep).astype(x_ref.dtype) / keep
    mask_ref[...] = mask
    out_ref[...] = x_ref[...] * mask


def dropout(x, ratio: float, seed) -> tuple:
    """Fused dropout. Returns (y, mask/keep) — mask is what backward
    multiplies by (matches autograd.Dropout's cached mask semantics).
    `seed`: int32 scalar; each grid block reseeds with (seed, block)."""
    keep = 1.0 - float(ratio)
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    lane = 128
    pad = (-n) % lane
    if pad:
        flat = jnp.pad(flat, (0, pad))
    x2 = flat.reshape(-1, lane)
    tile = _row_tile(x2.shape[0], lane)
    x2, r0 = _pad_rows(x2, tile)
    grid = (x2.shape[0] // tile,)
    seed_arr = jnp.asarray([seed], jnp.int32)
    y2, m2 = pl.pallas_call(
        functools.partial(_dropout_kernel, keep=keep),
        out_shape=(jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct(x2.shape, x.dtype)),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((tile, lane), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((tile, lane), lambda i: (i, 0)),
                   pl.BlockSpec((tile, lane), lambda i: (i, 0))),
        interpret=_interpret(),
        name="dropout",
    )(seed_arr, x2)
    y = y2.reshape(-1)[:n].reshape(orig_shape)
    m = m2.reshape(-1)[:n].reshape(orig_shape)
    return y, m


# ===========================================================================
# Top-K sparsification: histogram threshold + mask
# ===========================================================================
_BINS = 512


_HIST_CHUNK = 128  # bins counted per inner iteration (one lane row)


def _hist_kernel(x_ref, gmax_ref, hist_ref):
    # Revisiting-output accumulation: every grid step maps to the SAME
    # (_BINS/_HIST_CHUNK, _HIST_CHUNK) output block; zero it first,
    # then add this block's histogram of |x| over linear bins in
    # [0, gmax]. Bins are processed _HIST_CHUNK at a time so the
    # one-hot intermediate stays (n, 128) — VMEM-safe for any block
    # size — instead of a full (n, _BINS) expansion.
    @pl.when(pl.program_id(0) == 0)
    def _():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    a = jnp.abs(x_ref[...].astype(jnp.float32)).reshape(-1)
    gmax = gmax_ref[0]
    scale = jnp.where(gmax > 0, _BINS / gmax, 0.0)
    idx = jnp.clip((a * scale).astype(jnp.int32), 0, _BINS - 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (a.shape[0], _HIST_CHUNK),
                                    1)

    def chunk(c, _):
        base = c * _HIST_CHUNK
        counts = jnp.sum((lane + base == idx[:, None])
                         .astype(jnp.float32), axis=0)
        hist_ref[pl.dslice(c, 1), :] = (hist_ref[pl.dslice(c, 1), :]
                                        + counts[None, :])
        return 0

    jax.lax.fori_loop(0, _BINS // _HIST_CHUNK, chunk, 0)


def _mask_kernel(x_ref, thr_ref, out_ref):
    x = x_ref[...]
    thr = thr_ref[0]
    out_ref[...] = jnp.where(jnp.abs(x) >= thr, x, jnp.zeros_like(x))


def topk_threshold(flat, k: int):
    """Conservative top-K |g| threshold via a block-accumulated
    histogram (keeps >= k elements; all elements sharing the
    threshold bin survive — exact K would need a global sort, which
    the reference's encoder also avoids for large grads)."""
    n = flat.shape[0]
    gmax = jnp.max(jnp.abs(flat)).astype(jnp.float32)
    lane = 128
    pad = (-n) % lane
    x = jnp.pad(flat, (0, pad)) if pad else flat
    x2 = x.reshape(-1, lane)
    tile = _row_tile(x2.shape[0], lane, budget=_HIST_BUDGET)
    x2, _ = _pad_rows(x2, tile)
    grid = (x2.shape[0] // tile,)
    nrows = _BINS // _HIST_CHUNK
    hist = pl.pallas_call(
        _hist_kernel,
        out_shape=jax.ShapeDtypeStruct((nrows, _HIST_CHUNK),
                                       jnp.float32),
        grid=grid,
        in_specs=[pl.BlockSpec((tile, lane), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((nrows, _HIST_CHUNK), lambda i: (0, 0)),
        interpret=_interpret(),
        name="topk_hist",
    )(x2, jnp.asarray([1.0], jnp.float32) * gmax)
    # padding contributed zeros into bin 0; remove them
    hist = hist.reshape(_BINS).at[0].add(-(pad + (x2.size - x.size)))
    # threshold = lower edge of the first bin (from the top) where the
    # running count reaches k
    from_top = jnp.cumsum(hist[::-1])
    bin_from_top = jnp.argmax(from_top >= k)
    lower_edge = (_BINS - 1 - bin_from_top).astype(jnp.float32) \
        * gmax / _BINS
    return jnp.where(gmax > 0, lower_edge, jnp.float32(0.0))


def threshold_mask(x, thr):
    """Zero everything with |x| < thr (the sparsification select)."""
    orig = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    lane = 128
    pad = (-n) % lane
    if pad:
        flat = jnp.pad(flat, (0, pad))
    x2 = flat.reshape(-1, lane)
    tile = _row_tile(x2.shape[0], lane)
    x2, _ = _pad_rows(x2, tile)
    grid = (x2.shape[0] // tile,)
    y2 = pl.pallas_call(
        _mask_kernel,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((tile, lane), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((tile, lane), lambda i: (i, 0)),
        interpret=_interpret(),
        name="threshold_mask",
    )(x2, jnp.asarray(thr, jnp.float32).reshape(1))
    return y2.reshape(-1)[:n].reshape(orig)


# ===========================================================================
# Fused (flash-style) attention: softmax(qk^T/sqrt(d)) v with the score
# matrix living only in VMEM — never materialized to HBM. Forward saves
# just the per-row logsumexp; both backward kernels recompute the
# probabilities in-VMEM (the flash attention recipe). MXU does the four
# matmuls; padding and causality are iota masks.
# ===========================================================================
_ATTN_TQ = int(os.environ.get("SINGA_TPU_ATTN_TQ", "128"))
# query rows per grid step; env knob for tuning.  Validate HERE: a
# misaligned tile would otherwise surface as an opaque Mosaic
# BlockSpec rejection deep inside jit.
if _ATTN_TQ < 8 or _ATTN_TQ % 8:
    raise ValueError(
        f"SINGA_TPU_ATTN_TQ={_ATTN_TQ}: the flash-attention query "
        "tile must be a positive multiple of 8 (f32 sublane)")
_ATTN_VMEM_BUDGET = 6 * (1 << 20)  # bytes of k/v/q residents per head


def attn_supported(s: int, d: int) -> bool:
    """Route attention through the fused kernel only where it WINS:
    the head's K/V (and in backward, Q and dO) must fit the VMEM
    residency budget, and the sequence must clear the measured
    XLA crossover (~1024 on v5e — at 512 the kernel is 0.98x XLA;
    benchmarks/PALLAS_BENCH.md).  Long-context runs use ring
    attention anyway."""
    return (s >= _ATTN_MIN_SEQ
            and 4 * s * d * 4 <= _ATTN_VMEM_BUDGET)


def _attn_mask(scores, qi0, tq, sq, sk, causal):
    tq_, s_ = scores.shape
    qi = qi0 * tq + jax.lax.broadcasted_iota(jnp.int32, (tq_, s_), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (tq_, s_), 1)
    mask = (ki < sk) & (qi < sq)
    if causal:
        mask &= ki <= qi
    return jnp.where(mask, scores, -1e30)


def _mxu_precision(dtype, precision):
    """In-kernel dot precision. On the MXU a float32 dot at DEFAULT
    precision is ONE bf16 pass (operands rounded to bf16): measured
    on the v5e, that left the f32 kernel 1.4e-2 away from exact
    attention. So float32 operands follow the framework's matmul
    policy (`tensor.set_matmul_precision`: anything but "default"
    asks for fp32 parity — Mosaic offers DEFAULT and HIGHEST only),
    while bf16 operands are exact in one pass whatever the policy."""
    if dtype == jnp.float32 and precision in ("highest", "high"):
        return jax.lax.Precision.HIGHEST
    return None


def _dot(a, b, dims, prec):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                     causal, sq, sk, prec):
    q = q_ref[0].astype(jnp.float32) * scale      # (TQ, D)
    k = k_ref[0].astype(jnp.float32)              # (S, D)
    v = v_ref[0].astype(jnp.float32)
    s = _dot(q, k, ((1,), (1,)), prec)
    s = _attn_mask(s, pl.program_id(1), q.shape[0], sq, sk, causal)
    m = jnp.max(s, -1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, -1, keepdims=True)
    o_ref[0] = _dot(e / l, v, ((1,), (0,)), prec).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)


def _attn_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                    dq_ref, *, scale, causal, sq, sk, prec):
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    s = _dot(q * scale, k, ((1,), (1,)), prec)
    s = _attn_mask(s, pl.program_id(1), q.shape[0], sq, sk, causal)
    p = jnp.exp(s - lse_ref[0])                   # (TQ, S)
    dp = _dot(do, v, ((1,), (1,)), prec)
    ds = p * (dp - dl_ref[0])
    dq_ref[0] = (_dot(ds, k, ((1,), (0,)), prec)
                 * scale).astype(dq_ref.dtype)


def _attn_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dl_ref,
                     dk_ref, dv_ref, *, scale, causal, sq, sk, prec):
    k = k_ref[0].astype(jnp.float32)              # (TK, D)
    v = v_ref[0].astype(jnp.float32)
    q = q_ref[0].astype(jnp.float32)              # (S, D) full
    do = do_ref[0].astype(jnp.float32)
    s = _dot(q * scale, k, ((1,), (1,)), prec)
    # mask transposed relative to fwd: rows are queries, cols this k blk
    tk = k.shape[0]
    s_full = s.shape[0]
    qi = jax.lax.broadcasted_iota(jnp.int32, (s_full, tk), 0)
    ki = pl.program_id(1) * tk + jax.lax.broadcasted_iota(
        jnp.int32, (s_full, tk), 1)
    mask = (ki < sk) & (qi < sq)
    if causal:
        mask &= ki <= qi
    s = jnp.where(mask, s, -1e30)
    p = jnp.exp(s - lse_ref[0])                   # (S, TK)
    dv_ref[0] = _dot(p, do, ((0,), (0,)), prec).astype(dv_ref.dtype)
    dp = _dot(do, v, ((1,), (1,)), prec)
    ds = p * (dp - dl_ref[0])                     # (S, TK)
    dk_ref[0] = (_dot(ds, q, ((0,), (0,)), prec)
                 * scale).astype(dk_ref.dtype)


def _attn_shapes(q):
    b, h, s, d = q.shape
    # Block dims must be sublane-aligned for the input dtype (f32: 8,
    # bf16: 16, int8: 32 — use 32 to cover all) or Mosaic rejects the
    # BlockSpec at lowering.
    tq = _ATTN_TQ if s >= _ATTN_TQ else -(-s // 32) * 32
    spad = -(-s // tq) * tq
    return b, h, s, d, tq, spad


def _attn_pad(x, spad):
    b, h, s, d = x.shape
    if s == spad:
        return x.reshape(b * h, s, d)
    return jnp.pad(x, ((0, 0), (0, 0), (0, spad - s), (0, 0))) \
        .reshape(b * h, spad, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, scale=None,
                    precision=None):
    """Fused attention over [B, H, S, D]; same semantics as
    `parallel.ring_attention.plain_attention`, `precision` included
    (the framework's matmul policy string; see `_mxu_precision`)."""
    o, _ = _flash_fwd(q, k, v, causal, scale, precision)
    return o


def _flash_fwd(q, k, v, causal, scale, precision):
    b, h, s, d, tq, spad = _attn_shapes(q)
    sc = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    prec = _mxu_precision(q.dtype, precision)
    qp, kp, vp = (_attn_pad(x, spad) for x in (q, k, v))
    grid = (b * h, spad // tq)
    o, lse = pl.pallas_call(
        functools.partial(_attn_fwd_kernel, scale=sc, causal=causal,
                          sq=s, sk=s, prec=prec),
        out_shape=(jax.ShapeDtypeStruct((b * h, spad, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, spad, 1), jnp.float32)),
        grid=grid,
        in_specs=[pl.BlockSpec((1, tq, d), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((1, spad, d), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((1, spad, d), lambda i, j: (i, 0, 0))],
        out_specs=(pl.BlockSpec((1, tq, d), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, tq, 1), lambda i, j: (i, j, 0))),
        interpret=_interpret(),
        name="flash_fwd",
    )(qp, kp, vp)
    o = o.reshape(b, h, spad, d)[:, :, :s]
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, precision, res, g):
    q, k, v, o, lse = res
    b, h, s, d, tq, spad = _attn_shapes(q)
    sc = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    prec = _mxu_precision(q.dtype, precision)
    # delta_i = rowsum(dO_i * O_i) — the flash-bwd softmax correction
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    qp, kp, vp, gp = (_attn_pad(x, spad) for x in (q, k, v, g))
    dpad = jnp.pad(delta, ((0, 0), (0, 0), (0, spad - s), (0, 0))) \
        .reshape(b * h, spad, 1) if spad != s else \
        delta.reshape(b * h, spad, 1)
    grid = (b * h, spad // tq)
    blk = lambda i, j: (i, j, 0)       # noqa: E731
    full = lambda i, j: (i, 0, 0)      # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_attn_dq_kernel, scale=sc, causal=causal,
                          sq=s, sk=s, prec=prec),
        out_shape=jax.ShapeDtypeStruct((b * h, spad, d), q.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((1, tq, d), blk),
                  pl.BlockSpec((1, spad, d), full),
                  pl.BlockSpec((1, spad, d), full),
                  pl.BlockSpec((1, tq, d), blk),
                  pl.BlockSpec((1, tq, 1), blk),
                  pl.BlockSpec((1, tq, 1), blk)],
        out_specs=pl.BlockSpec((1, tq, d), blk),
        interpret=_interpret(),
        name="flash_dq",
    )(qp, kp, vp, gp, lse, dpad)
    dk, dv = pl.pallas_call(
        functools.partial(_attn_dkv_kernel, scale=sc, causal=causal,
                          sq=s, sk=s, prec=prec),
        out_shape=(jax.ShapeDtypeStruct((b * h, spad, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, spad, d), v.dtype)),
        grid=grid,
        in_specs=[pl.BlockSpec((1, tq, d), blk),
                  pl.BlockSpec((1, tq, d), blk),
                  pl.BlockSpec((1, spad, d), full),
                  pl.BlockSpec((1, spad, d), full),
                  pl.BlockSpec((1, spad, 1), full),
                  pl.BlockSpec((1, spad, 1), full)],
        out_specs=(pl.BlockSpec((1, tq, d), blk),
                   pl.BlockSpec((1, tq, d), blk)),
        interpret=_interpret(),
        name="flash_dkv",
    )(kp, vp, qp, gp, lse, dpad)
    unpad = lambda x: x.reshape(b, h, spad, d)[:, :, :s]  # noqa: E731
    return unpad(dq), unpad(dk), unpad(dv)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def topk_sparsify(x, spars: float):
    """Keep the ~top spars-fraction of |x| (reference:
    `fusedSparsification(topK=true)`), zeroing the rest."""
    flat = x.reshape(-1)
    k = max(1, int(flat.shape[0] * spars))
    thr = topk_threshold(flat, k)
    return threshold_mask(x, thr)


# -- one position of a decode cache a row, in place ---------------------------
def _cache_write_kernel(at_ref, cache_ref, new_ref, out_ref, *, axis, tb):
    """The block of the row's cache that holds position at[b], with
    that position replaced by `new`: a select over the whole block, so
    no store has a dynamic lane or sublane."""
    here = at_ref[pl.program_id(0)] % tb
    blk = cache_ref[...]
    where = jax.lax.broadcasted_iota(jnp.int32, blk.shape, axis) == here
    out_ref[...] = jnp.where(where, new_ref[...], blk)


def cache_write(cache, new, at, axis):
    """`cache` [B, H, ...] with position `at[b]` of its axis `axis` (2
    or 3: [B,H,T,D] holds a position as a row, [B,H,D,T] as a column)
    set to `new` [B, H, D], row by row, IN PLACE: the result aliases
    `cache` (donate it), and of each row only the 128-position block
    around `at[b]` crosses VMEM. XLA's scatter would do the same write
    after copying the whole cache into a layout of its own and back,
    twice the cache's bytes a step (PERF.md, PR 27)."""
    B, H = cache.shape[:2]
    T = cache.shape[axis]
    tb = min(128, T)
    if T % tb:
        raise ValueError(f"cache_write: {T} positions do not divide "
                         f"into blocks of {tb}")
    block = list(cache.shape)
    block[0], block[axis] = 1, tb
    new = jnp.expand_dims(new, axis).astype(cache.dtype)
    one = list(new.shape)
    one[0] = 1

    def cache_map(b, at_ref):
        idx = [b, 0, 0, 0]
        idx[axis] = at_ref[b] // tb
        return tuple(idx)

    return pl.pallas_call(
        functools.partial(_cache_write_kernel, axis=axis, tb=tb),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[pl.BlockSpec(tuple(block), cache_map),
                      pl.BlockSpec(tuple(one),
                                   lambda b, at_ref: (b, 0, 0, 0))],
            out_specs=pl.BlockSpec(tuple(block), cache_map)),
        input_output_aliases={1: 0},
        interpret=_interpret(),
        name="cache_write",
    )(at.astype(jnp.int32), cache, new)


# -- a closing chunk's pooled key and value into a summary list, in place ------
def _chunk_summary_kernel(at_ref, to_ref, closes_ref, k_ref, v_ref, phi_ref,
                          mu_ref, sk_ref, sv_ref, sk_out, sv_out, *, per,
                          tb, ts):
    """A row's group of heads: the `per` buffer entries of the chunk
    that holds position at[b], pooled with weights softmax(k . phi)
    over them; where the row's chunk closes, the pooled key (+ mu) and
    value replace entry to[b] of its summary block, else the block goes
    back as it came. Everything is a select or a sum over a whole block
    of lanes: no load or store has a dynamic lane."""
    b = pl.program_id(0)
    here = at_ref[b] % tb
    lo = here - here % per
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1)
    inside = (lane >= lo) & (lane < lo + per)
    hit = ((jax.lax.broadcasted_iota(jnp.int32, sk_ref.shape[2:], 1)
            == to_ref[b] % ts) & (closes_ref[b] != 0))
    for h in range(k_ref.shape[1]):
        k = k_ref[0, h].astype(jnp.float32)                  # [D, tb]
        v = v_ref[0, h].astype(jnp.float32)
        logit = jnp.where(inside, jnp.sum(k * phi_ref[h], axis=0,
                                          keepdims=True), -1e30)
        e = jnp.where(inside, jnp.exp(
            logit - jnp.max(logit, axis=1, keepdims=True)), 0.0)
        a = e / jnp.sum(e, axis=1, keepdims=True)            # [1, tb]
        pk = jnp.sum(k * a, axis=1, keepdims=True) + mu_ref[h]   # [D, 1]
        pv = jnp.sum(v * a, axis=1, keepdims=True)
        sk_out[0, h] = jnp.where(hit, pk.astype(sk_out.dtype), sk_ref[0, h])
        sv_out[0, h] = jnp.where(hit, pv.astype(sv_out.dtype), sv_ref[0, h])


@functools.partial(jax.jit, static_argnames=("per",))
def chunk_summary(k_buf, v_buf, sk, sv, phi, mu, at, to, closes, per):
    """Row b's chunk of `per` buffer entries around position at[b] of
    `k_buf`, `v_buf` [B, H, D, W] (aligned: entries at[b] - at[b] % per
    ...), pooled over its positions with weights softmax_j(k_j . phi):
    sum_j a_j k_j + mu and sum_j a_j v_j (`phi`, `mu` [H, D] float32),
    written as entry to[b] of the summary lists `sk`, `sv` [B, H, D, R]
    for the rows with `closes[b]`, IN PLACE: the results alias `sk` and
    `sv` (donate them), a row whose chunk stays open keeps what its
    entry held, and of each row only the 128-position blocks around
    at[b] and to[b] cross VMEM. XLA's own gather of the chunk re-lays
    the whole buffer first, positions major, and its gather of the held
    entry the whole list (compiled for a described v5e, PR 35)."""
    B, H, D, W = k_buf.shape
    R = sk.shape[3]
    tb, ts = min(128, W), min(128, R)
    if W % tb or R % ts or tb % per:
        raise ValueError(f"chunk_summary: a buffer of {W} and a list of {R} "
                         f"entries in blocks of {tb} and {ts}, chunks of "
                         f"{per}")
    hb = 8 if H % 8 == 0 else H

    def buf_map(b, g, at_ref, to_ref, closes_ref):
        return b, g, 0, at_ref[b] // tb

    def list_map(b, g, at_ref, to_ref, closes_ref):
        return b, g, 0, to_ref[b] // ts

    def head_map(b, g, *_):
        return g, 0, 0

    column = pl.BlockSpec((hb, D, 1), head_map)
    return pl.pallas_call(
        functools.partial(_chunk_summary_kernel, per=per, tb=tb, ts=ts),
        out_shape=(jax.ShapeDtypeStruct(sk.shape, sk.dtype),
                   jax.ShapeDtypeStruct(sv.shape, sv.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, H // hb),
            in_specs=[pl.BlockSpec((1, hb, D, tb), buf_map),
                      pl.BlockSpec((1, hb, D, tb), buf_map),
                      column, column,
                      pl.BlockSpec((1, hb, D, ts), list_map),
                      pl.BlockSpec((1, hb, D, ts), list_map)],
            out_specs=(pl.BlockSpec((1, hb, D, ts), list_map),
                       pl.BlockSpec((1, hb, D, ts), list_map))),
        input_output_aliases={7: 0, 8: 1},
        name="chunk_summary",
        interpret=_interpret(),
    )(at.astype(jnp.int32), to.astype(jnp.int32), closes.astype(jnp.int32),
      k_buf, v_buf, phi.astype(jnp.float32)[:, :, None],
      mu.astype(jnp.float32)[:, :, None], sk, sv)


# -- a block of a prompt over itself and a list of summaries, one softmax ------
_BLOCK_ATTEND_TQ = 256   # queries a tile, and keys a pass over the block


def _block_attend_kernel(seen_ref, q_ref, k_ref, v_ref, sk_ref, sv_ref,
                         o_ref, *, scale, tq, ts, prec):
    """One head of one row, one tile of `tq` queries: the block's keys
    up to the tile's own (whole tiles before it, the causal triangle on
    it), then the first `seen` summaries `ts` at a time, folded into
    one running softmax in float32; no score leaves VMEM."""
    i = pl.program_id(1)
    q = q_ref[0]

    def fold(carry, kk, vv, mask):
        m, l, acc = carry
        s = _dot(q, kk, ((1,), (1,)), prec) * scale
        if mask is not None:
            s = jnp.where(mask, s, -1e30)
        m2 = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        p, a = jnp.exp(s - m2), jnp.exp(m - m2)
        return (m2, a * l + jnp.sum(p, -1, keepdims=True),
                a * acc + _dot(p.astype(vv.dtype), vv, ((1,), (0,)), prec))

    def keys(j, carry, mask=None):
        at = pl.ds(pl.multiple_of(j * tq, tq), tq)
        return fold(carry, k_ref[0, at], v_ref[0, at], mask)

    def summaries(j, carry):
        at = pl.ds(pl.multiple_of(j * ts, ts), ts)
        col = j * ts + jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 1)
        return fold(carry, sk_ref[0, at], sv_ref[0, at], col < seen_ref[0])

    carry = (jnp.full((tq, 1), -1e30, jnp.float32),
             jnp.zeros((tq, 1), jnp.float32),
             jnp.zeros((tq, q.shape[1]), jnp.float32))
    carry = jax.lax.fori_loop(0, i, keys, carry)
    row = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1)
    carry = keys(i, carry, col <= row)
    _, l, acc = jax.lax.fori_loop(0, pl.cdiv(seen_ref[0], ts), summaries,
                                  carry)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def block_attend(q, k, v, sk, sv, seen, precision=None):
    """Causal self-attention over ONE block of a prompt whose queries
    also see, of everything before the block, one pooled key and value
    a chunk of positions, all in one softmax: q, k, v [B, H, L, D] at
    the block's L positions; sk, sv [B, H, R, D], a summary list of
    which the first `seen` (a traced scalar) entries are those of the
    earlier blocks -> [B, H, L, D]. The scores of a tile of queries
    stay in VMEM and the tiles of keys behind a query's own are never
    read, nor the summaries past `seen`: XLA's own softmax wrote every
    [256, L + R] tile of scores to HBM and read it back three times,
    37 % of a prefill (PERF.md, PR 35)."""
    B, H, L, D = q.shape
    R = sk.shape[2]
    tq = min(_BLOCK_ATTEND_TQ, L)
    ts = min(128, R)
    if L % tq or R % ts:
        raise ValueError(f"block_attend: a block of {L} positions and a "
                         f"list of {R} summaries in tiles of {tq} and {ts}")

    def whole(n):
        return pl.BlockSpec((1, n, D), lambda b, i, seen_ref: (b, 0, 0))

    tile = pl.BlockSpec((1, tq, D), lambda b, i, seen_ref: (b, i, 0))
    out = pl.pallas_call(
        functools.partial(_block_attend_kernel, scale=1.0 / (D ** 0.5),
                          tq=tq, ts=ts,
                          prec=_mxu_precision(q.dtype, precision)),
        out_shape=jax.ShapeDtypeStruct((B * H, L, D), v.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B * H, L // tq),
            in_specs=[tile, whole(L), whole(L), whole(R), whole(R)],
            out_specs=tile),
        name="block_attend",
        interpret=_interpret(),
    )(jnp.reshape(seen, (1,)).astype(jnp.int32),
      *(t.reshape(B * H, t.shape[2], D) for t in (q, k, v, sk, sv)))
    return out.reshape(B, H, L, D)


# -- one query a row against the positions that row has written ---------------
DECODE_ATTEND_BLOCK = 128  # positions a block: a lane tile, `cache_write`'s
_DECODE_ATTEND_SLOTS = 4    # blocks in flight or in use at a time
# the fewest blocks a rung must have for the kernel to be the faster
# path (one block: nothing to skip)
DECODE_ATTEND_MIN_BLOCKS = 2


def decode_attend_blocks(T, dtype=jnp.float32):
    """How many blocks `decode_attend` cuts a rung of `T` positions
    into; 0 where the rung is read whole instead (it has fewer than
    `DECODE_ATTEND_MIN_BLOCKS`, or no whole number of them, or is not
    float32)."""
    n = T // DECODE_ATTEND_BLOCK
    return 0 if (T % DECODE_ATTEND_BLOCK or n < DECODE_ATTEND_MIN_BLOCKS
                 or dtype != jnp.float32) else n


def _decode_attend_kernel(pos_ref, q_ref, layer_ref, out_ref, buf, sem,
                          qb, s_scr, acc, *, scale, neg, tb, nslots):
    """Every row in turn: its key blocks 0..pos[b]//tb (scores into
    `s_scr`), one exact softmax over the rung, its value blocks (sum
    into `acc`), its output. The blocks come from HBM by DMAs of the
    kernel's own, `nslots - 1` ahead of the one in use and straight on
    from one row's last block to the next row's first, so a row costs
    the blocks it has and nothing a grid step. All arithmetic is
    float32 on the vector unit: q is spread over the lanes once a row
    (`qb`), a key block is multiplied by it and summed over D, a value
    block by the row of probabilities and summed over the lanes at the
    row's end."""
    B, D, H = q_ref.shape
    nblk = s_scr.shape[0]

    # `lax.div` / `lax.rem` on what is never negative: one operation
    # each where `//` and `%` lower through a sign correction, which is
    # most of what lowering this kernel costs a program that holds it
    def blocks(b):
        # of a row; never past the rung, whatever `pos` says, and the
        # cursor that runs ahead of the last row reads no `pos` past it
        at = pos_ref[jnp.minimum(b, B - 1)]
        return jnp.minimum(jax.lax.div(at, tb), nblk - 1) + 1

    def dma(b, j, slot):
        # item j of row b: key block j, then value block j - blocks(b)
        n = blocks(b)
        kv = (j >= n).astype(jnp.int32)
        at = pl.multiple_of((j - kv * n) * tb, tb)
        return pltpu.make_async_copy(
            layer_ref.at[kv, b, :, :, pl.ds(at, tb)], buf.at[slot],
            sem.at[slot])

    def after(b, j):
        last = j + 1 == 2 * blocks(b)
        return jnp.where(last, b + 1, b), jnp.where(last, 0, j + 1)

    total = jax.lax.fori_loop(0, B, lambda b, n: n + 2 * blocks(b), 0)

    def keys(b, j, slot):
        @pl.when(j == 0)
        def _():
            for h in range(H):
                qb[h] = jnp.broadcast_to(q_ref[b, :, h:h + 1], (D, tb))

        for h in range(H):
            s_scr[j, h] = jnp.sum(buf[slot, h] * qb[h], axis=0,
                                  keepdims=True)

        @pl.when(j == blocks(b) - 1)
        def _():
            s = s_scr[...] * scale                       # [nblk, H, 1, tb]
            where = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) * tb
                     + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3))
            s = jnp.where(where <= pos_ref[b], s, neg)
            m = jnp.max(jnp.max(s, axis=3, keepdims=True), axis=0,
                        keepdims=True)
            e = jnp.exp(s - m)
            s_scr[...] = e / jnp.sum(jnp.sum(e, axis=3, keepdims=True),
                                     axis=0, keepdims=True)

    def values(b, j, slot):
        for h in range(H):
            pv = buf[slot, h] * s_scr[j, h]              # [D, tb] * [1, tb]
            # a row's first block starts the sum (what `acc` held is
            # the row before's, or nothing)
            acc[h] = jnp.where(j == 0, pv, acc[h] + pv)

        @pl.when(j == blocks(b) - 1)
        def _():
            lane = jax.lax.broadcasted_iota(jnp.int32, (D, H), 1)
            o = jnp.zeros((D, H), jnp.float32)
            for h in range(H):
                o = jnp.where(lane == h,
                              jnp.sum(acc[h], axis=1, keepdims=True), o)
            out_ref[b] = o

    def start(i, cur):
        b, j = cur

        @pl.when(i < total)
        def _():
            dma(b, j, jax.lax.rem(i, nslots)).start()

        return after(b, j)

    def step(i, carry):
        (b, j), ahead = carry
        slot = jax.lax.rem(i, nslots)
        dma(b, j, slot).wait()
        n = blocks(b)

        @pl.when(j < n)
        def _():
            keys(b, j, slot)

        @pl.when(j >= n)
        def _():
            values(b, j - n, slot)

        # the slot just read is the one the next DMA fills
        return after(b, j), start(i + nslots, ahead)

    zero = (jnp.int32(0), jnp.int32(0))
    ahead = zero
    for i in range(nslots):
        ahead = start(jnp.int32(i), ahead)
    jax.lax.fori_loop(0, total, step, (zero, ahead))


@functools.partial(jax.jit, static_argnames=("scale",))
def decode_attend(layer, q, pos, scale):
    """softmax(q . K[:pos+1] * scale) . V[:pos+1] a row: `layer`
    [2, B, H, D, T] float32 as the slab stores it (keys at [0], values
    at [1], positions last), `q` [B, H, D], `pos` [B] int32 -> [B, H,
    D] float32. Of row b only the blocks 0..pos[b] // 128 leave HBM;
    the last block's tail beyond pos[b] is masked with the constant
    `_slot_step` masks with, and every product is a float32 product
    whatever the matmul policy says (the policy rounds operands for
    the matrix unit; nothing here runs on it)."""
    _, B, H, D, T = layer.shape
    tb = DECODE_ATTEND_BLOCK
    if T % tb:
        raise ValueError(f"decode_attend: {T} positions do not divide "
                         f"into blocks of {tb}")
    nslots = _DECODE_ATTEND_SLOTS
    neg = float(jnp.finfo(jnp.float32).min / 2)
    out = pl.pallas_call(
        functools.partial(_decode_attend_kernel, scale=float(scale),
                          neg=neg, tb=tb, nslots=nslots),
        out_shape=jax.ShapeDtypeStruct((B, D, H), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((nslots, H, D, tb), jnp.float32),
                pltpu.SemaphoreType.DMA((nslots,)),
                pltpu.VMEM((H, D, tb), jnp.float32),
                pltpu.VMEM((T // tb, H, 1, tb), jnp.float32),
                pltpu.VMEM((H, D, tb), jnp.float32)]),
        name="decode_attend",
        interpret=_interpret(),
    )(pos.astype(jnp.int32), jnp.swapaxes(q, 1, 2).astype(jnp.float32),
      layer)
    return jnp.swapaxes(out, 1, 2)


# -- one query a row against its window buffer and the summaries it sees -------
_WINDOW_ATTEND_SLOTS = 4    # blocks in flight or in use at a time
_WINDOW_ATTEND_HEADS = 8    # heads a pass of the loop: a tile of score rows


def window_summary_blocks(W, R):
    """(blocks of a window buffer of `W` positions, blocks of a summary
    list of `R` entries) as `window_summary_attend` cuts them; (0, 0)
    where buffer and list are read whole instead (either is no whole
    number of blocks, or the buffer has fewer than
    `DECODE_ATTEND_MIN_BLOCKS`: nothing to skip)."""
    tb = DECODE_ATTEND_BLOCK
    if W % tb or R % tb or not R or W // tb < DECODE_ATTEND_MIN_BLOCKS:
        return 0, 0
    return W // tb, R // tb


def _window_rows(at, seen, W, R):
    """A row's lengths clamped to its arrays, and the blocks of its
    buffer and of its list that hold them: (at, seen, buffer blocks,
    list blocks), each [B] int32. Never past either array, whatever
    `at` and `seen` say."""
    tb = DECODE_ATTEND_BLOCK
    at = jnp.clip(at, 0, W - 1).astype(jnp.int32)
    seen = jnp.clip(seen, 0, R).astype(jnp.int32)
    return at, seen, at // tb + 1, (seen + tb - 1) // tb


def window_summary_entries_read(at, seen, W, R):
    """Entries of the blocks `window_summary_attend` fetches for rows
    at `at`, `seen` [B] (keys; as many values), summed over the rows."""
    _, _, n, ns = _window_rows(at, seen, W, R)
    return DECODE_ATTEND_BLOCK * jnp.sum(n + ns)


def _window_summary_attend_kernel(at_ref, seen_ref, n_ref, ns_ref, q_ref,
                                  k_ref, v_ref, sk_ref, sv_ref, out_ref, buf,
                                  sem, qb, s_scr, acc, *, scale, neg, tb,
                                  nslots):
    """Every row in turn: its buffer's key blocks 0..n[b]-1, its list's
    key blocks 0..ns[b]-1 (scores into `s_scr`), one exact softmax over
    the entries 0..at[b] and the first seen[b] summaries, the same
    blocks of the two value arrays (sum into `acc`), its output. The
    blocks come from HBM by DMAs of the kernel's own, `nslots - 1`
    ahead of the one in use and straight on from one row's last block
    to the next row's first, whichever of the four arrays holds it: a
    row costs the blocks it has and nothing a grid step. All arithmetic
    is float32 on the vector unit, a tile of `hb` heads a pass of a
    loop: q is spread over the lanes once a row (`qb`), a key block is
    multiplied by it and summed over D, a value block by its row of
    probabilities (rounded to the values' dtype first) and summed over
    the lanes at the row's end."""
    B, H, D = q_ref.shape
    nblk, G, hb, _ = s_scr.shape
    nbuf = k_ref.shape[3] // tb
    sources = (k_ref, sk_ref, v_ref, sv_ref)

    def blocks(b):
        # of a row's buffer and of its list; the cursor that runs ahead
        # of the last row reads nothing past it
        b = jnp.minimum(b, B - 1)
        return n_ref[b], ns_ref[b]

    def item(b, j):
        # item j of row b: buffer keys, list keys, buffer values, list
        # values -> (is a value, is of the list, block of its array)
        n, ns = blocks(b)
        val = j >= n + ns
        j = j - jnp.where(val, n + ns, 0)
        lst = j >= n
        return val, lst, j - jnp.where(lst, n, 0)

    def dma(src, b, blk, slot):
        at = pl.multiple_of(blk * tb, tb)
        return pltpu.make_async_copy(
            src.at[b, :, :, pl.ds(at, tb)], buf.at[slot], sem.at[slot])

    def after(b, j):
        n, ns = blocks(b)
        last = j + 1 == 2 * (n + ns)
        return jnp.where(last, b + 1, b), jnp.where(last, 0, j + 1)

    total = jax.lax.fori_loop(
        0, B, lambda b, n: n + 2 * sum(blocks(b)), jnp.int32(0))

    def heads(body):
        jax.lax.fori_loop(0, G, lambda g, _: body(g) or 0, 0)

    def spread(b):
        qt = q_ref[b].T                                     # [D, H]
        for h in range(H):
            qb[h] = jnp.broadcast_to(qt[:, h:h + 1], (D, tb))

    def keys(slot, row):
        def tile(g):
            s_scr[row, g] = jnp.concatenate([
                jnp.sum(buf[slot, g * hb + i].astype(jnp.float32)
                        * qb[g * hb + i], axis=0, keepdims=True)
                for i in range(hb)], 0)
        heads(tile)

    def softmax(b):
        s = s_scr[...] * scale                          # [nblk, G, hb, tb]
        blk = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        local = blk < nbuf
        where = jnp.where(local, blk, blk - nbuf) * tb + lane
        s = jnp.where(
            where <= jnp.where(local, at_ref[b], seen_ref[b] - 1), s, neg)
        m = jnp.max(jnp.max(s, axis=3, keepdims=True), axis=0,
                    keepdims=True)
        e = jnp.exp(s - m)
        p = e / jnp.sum(jnp.sum(e, axis=3, keepdims=True), axis=0,
                        keepdims=True)
        s_scr[...] = p.astype(buf.dtype).astype(jnp.float32)
        acc[...] = jnp.zeros_like(acc)

    def values(slot, row):
        def tile(g):
            p = s_scr[row, g]                               # [hb, tb]
            for i in range(hb):
                h = g * hb + i
                acc[h] += buf[slot, h].astype(jnp.float32) * p[i:i + 1]
        heads(tile)

    def result(b):
        lane = jax.lax.broadcasted_iota(jnp.int32, (D, H), 1)

        def tile(g, o):
            for i in range(hb):
                h = g * hb + i
                o = jnp.where(lane == h,
                              jnp.sum(acc[h], axis=1, keepdims=True), o)
            return o

        out_ref[b] = jax.lax.fori_loop(
            0, G, tile, jnp.zeros((D, H), jnp.float32)).T

    def start(i, cur):
        b, j = cur

        @pl.when(i < total)
        def _():
            val, lst, blk = item(b, j)
            kind = 2 * val.astype(jnp.int32) + lst.astype(jnp.int32)
            for which, src in enumerate(sources):
                @pl.when(kind == which)
                def _():
                    dma(src, b, blk, jax.lax.rem(i, nslots)).start()

        return after(b, j)

    def step(i, carry):
        (b, j), ahead = carry
        slot = jax.lax.rem(i, nslots)
        dma(k_ref, 0, 0, slot).wait()      # whichever array it came from
        half = sum(blocks(b))
        val, lst, blk = item(b, j)
        row = blk + jnp.where(lst, nbuf, 0)

        @pl.when(j == 0)
        def _():
            spread(b)

        @pl.when(jnp.logical_not(val))
        def _():
            keys(slot, row)

        @pl.when(j == half - 1)
        def _():
            softmax(b)

        @pl.when(val)
        def _():
            values(slot, row)

        @pl.when(j == 2 * half - 1)
        def _():
            result(b)

        # the slot just read is the one the next DMA fills
        return after(b, j), start(i + nslots, ahead)

    zero = (jnp.int32(0), jnp.int32(0))
    ahead = zero
    for i in range(nslots):
        ahead = start(jnp.int32(i), ahead)
    jax.lax.fori_loop(0, total, step, (zero, ahead))


@jax.jit
def window_summary_attend(q, k_buf, v_buf, sk, sv, at, seen):
    """One softmax a row over its window buffer's entries 0..at[b] and
    the first seen[b] entries of its summary list: softmax([q . K,
    q . SK] / sqrt(D)) . [V, SV]. `q` [B, H, D]; `k_buf`, `v_buf` [B,
    H, D, W] and `sk`, `sv` [B, H, D, R] as the slab stores them
    (positions last, any float dtype); `at`, `seen` [B] int32 -> [B, H,
    D] float32. Of row b only the buffer's blocks 0..at[b] // 128 and
    the list's blocks 0..ceil(seen[b] / 128) - 1 leave HBM; a block's
    tail beyond `at[b]` or `seen[b]` is masked entry by entry. Scores,
    softmax and both weighted sums are float32 whatever the matmul
    policy says (nothing here runs on the matrix unit); the
    probabilities are rounded to the values' dtype before they weigh
    the values. The two `einsum`s this stands for read every buffer and
    every list whole: 9.66 GB a step where the rows of
    `evabyte-serve-longctx32` need 36 % of them (PERF.md, PR 36)."""
    B, H, D, W = k_buf.shape
    R = sk.shape[3]
    tb = DECODE_ATTEND_BLOCK
    nbuf, nlist = window_summary_blocks(W, R)
    if not nbuf:
        raise ValueError(f"window_summary_attend: a buffer of {W} and a "
                         f"list of {R} entries do not divide into blocks of "
                         f"{tb}, or the buffer has fewer than "
                         f"{DECODE_ATTEND_MIN_BLOCKS}")
    nslots = _WINDOW_ATTEND_SLOTS
    hb = _WINDOW_ATTEND_HEADS if H % _WINDOW_ATTEND_HEADS == 0 else H
    return pl.pallas_call(
        functools.partial(_window_summary_attend_kernel,
                          scale=1.0 / (D ** 0.5),
                          neg=float(jnp.finfo(jnp.float32).min / 2),
                          tb=tb, nslots=nslots),
        out_shape=jax.ShapeDtypeStruct((B, H, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 4,
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((nslots, H, D, tb), k_buf.dtype),
                pltpu.SemaphoreType.DMA((nslots,)),
                pltpu.VMEM((H, D, tb), jnp.float32),
                pltpu.VMEM((nbuf + nlist, H // hb, hb, tb), jnp.float32),
                pltpu.VMEM((H, D, tb), jnp.float32)]),
        name="window_summary_attend",
        interpret=_interpret(),
    )(*_window_rows(at, seen, W, R), q.astype(jnp.float32),
      k_buf, v_buf, sk, sv)


# -- one query a row and group against the blocks an indexer selected --------
def _selected_blocks_kernel(sel_ref, n_ref, pos_ref, q_ref, *refs, scale, tb,
                            groups, nsel):
    """Grid step (b, j): of every group g of row b, the j-th selected
    block of keys and values (the pipeline fetched it by the block id
    in `sel`), folded into the group's running softmax; a block past
    the group's count is its last one again (no fetch) and skipped.
    The block that holds pos[b] is masked past it."""
    k_refs, v_refs = refs[:groups], refs[groups:2 * groups]
    o_ref, m_scr, l_scr, acc = refs[2 * groups:]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, -1e30, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    for g in range(groups):
        @pl.when(j < n_ref[b * groups + g])
        def _(g=g):
            s = jax.lax.dot_general(
                q_ref[0, g], k_refs[g][0, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [Hg, tb]
            at = (sel_ref[(b * groups + g) * nsel + j] * tb
                  + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(at <= pos_ref[b], s, -1e30)
            m_old = jnp.max(m_scr[g], axis=1, keepdims=True)     # [Hg, 1]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            a = jnp.exp(m_old - m_new)
            l_scr[g] = a * l_scr[g] + jnp.sum(p, axis=1, keepdims=True)
            acc[g] = a * acc[g] + jax.lax.dot_general(
                p.astype(v_refs[g].dtype), v_refs[g][0, 0],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_scr[g] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    @pl.when(j == nsel - 1)
    def _():
        o_ref[0] = acc[...] / jnp.max(l_scr[...], axis=2, keepdims=True)


def selected_blocks_lower(block, T):
    """Whether `selected_blocks_attend` lowers for blocks of `block`
    positions on a rung of `T`: a block is a whole number of lane tiles
    and the rung a whole number of blocks."""
    return block % DECODE_ATTEND_BLOCK == 0 and T % block == 0


@functools.partial(jax.jit, static_argnames=("block",))
def selected_blocks_attend(q, k, v, sel, n_sel, pos, block):
    """softmax(q . K[blocks] / sqrt(D)) . V[blocks] for each row b and
    key/value group g over the blocks `sel[b, g, :n_sel[b, g]]` (ids of
    `block`-position blocks) of its keys and values, positions past
    pos[b] masked: `q` [B, G, Hg, D] (the group's query heads), `k` [B,
    G, D, T] and `v` [B, G, T, D] as the slab stores them, `sel` [B, G,
    S] int32, `n_sel` [B, G] (at least 1), `pos` [B] -> [B, G, Hg, D]
    float32. Of each row and group only the selected blocks leave HBM,
    a grid step a selection rank with every group's block at once; one
    running softmax in float32, the probabilities rounded to the
    values' dtype before they weigh them. Interpreted, any block that
    divides the rung; lowered by Mosaic, `selected_blocks_lower`."""
    B, G, Hg, D = q.shape
    T = k.shape[3]
    S = sel.shape[2]
    tb = int(block)
    if T % tb:
        raise ValueError(f"selected_blocks_attend: {T} positions do not "
                         f"divide into blocks of {tb}")

    def key_map(g):
        def index(b, j, sel_ref, n_ref, pos_ref):
            r = b * G + g
            return b, g, 0, sel_ref[r * S + jnp.minimum(j, n_ref[r] - 1)]
        return index

    def value_map(g):
        def index(b, j, sel_ref, n_ref, pos_ref):
            r = b * G + g
            return b, g, sel_ref[r * S + jnp.minimum(j, n_ref[r] - 1)], 0
        return index

    def row(b, j, *_):
        return b, 0, 0, 0

    return pl.pallas_call(
        functools.partial(_selected_blocks_kernel, scale=1.0 / (D ** 0.5),
                          tb=tb, groups=G, nsel=S),
        out_shape=jax.ShapeDtypeStruct((B, G, Hg, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, S),
            in_specs=[pl.BlockSpec((1, G, Hg, D), row)]
            + [pl.BlockSpec((1, 1, D, tb), key_map(g)) for g in range(G)]
            + [pl.BlockSpec((1, 1, tb, D), value_map(g)) for g in range(G)],
            out_specs=pl.BlockSpec((1, G, Hg, D), row),
            scratch_shapes=[pltpu.VMEM((G, Hg, 128), jnp.float32),
                            pltpu.VMEM((G, Hg, 128), jnp.float32),
                            pltpu.VMEM((G, Hg, D), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="selected_blocks_attend",
        interpret=_interpret(),
    )(sel.reshape(-1).astype(jnp.int32), n_sel.reshape(-1).astype(jnp.int32),
      pos.astype(jnp.int32), q, *([k] * G), *([v] * G))


# -- one step of the gated delta rule a row and value head, in place ----------
_GATED_DELTA_HEADS = 16     # value heads a grid step: 1 MiB of float32 states


def _gated_delta_kernel(qk_ref, vgb_ref, s_ref, o_ref, s_out, *, heads):
    """Grid step (b, j): value heads j*hb .. j*hb + hb - 1 of row b. A
    head's rows of `qk` are q and k, of `vgb` v, exp(g) and beta (the
    last two the same in every lane); its state S [Dk, Dv] crosses
    VMEM once: S <- e S;  S <- S + k (x) beta (v - S^T k);  o = S^T q,
    in float32 on the vector units. A row r [1, Dk] becomes the column
    r^T [Dk, 1] by the identity's mask and a sum along the lanes, so
    no operation moves data across the tile."""
    dk = s_ref.shape[2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))

    def column(row):
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    for h in range(heads):
        qc = column(qk_ref[0, 2 * h:2 * h + 1, :])          # [Dk, 1]
        kc = column(qk_ref[0, 2 * h + 1:2 * h + 2, :])
        v = vgb_ref[0, 3 * h:3 * h + 1, :]                  # [1, Dv]
        e = vgb_ref[0, 3 * h + 1:3 * h + 2, :]
        beta = vgb_ref[0, 3 * h + 2:3 * h + 3, :]
        s = s_ref[0, h].astype(jnp.float32) * e             # [Dk, Dv]
        d = beta * (v - jnp.sum(s * kc, axis=0, keepdims=True))
        s = s + kc * d
        o_ref[0, h:h + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)
        s_out[0, h] = s.astype(s_out.dtype)


@jax.jit
def gated_delta_step(q, k, v, g, beta, state):
    """One position of the gated delta rule for every row b and value
    head h: q, k [B, H, Dk] (q already l2-normed and scaled, k
    l2-normed), v [B, H, Dv], g and beta [B, H] (float32), the states
    `state` [B, H, Dk, Dv] (float32, or the dtype the slab holds them
    in: read and written in it, computed in float32):

        S <- exp(g) S;   S <- S + k (x) beta (v - S^T k);   o = S^T q

    Returns (o [B, H, Dv] float32, the new states). The states are
    updated IN PLACE: the result aliases `state` (donate it), and each
    row's states cross VMEM once in and once out, `_GATED_DELTA_HEADS`
    heads a grid step."""
    B, H, Dk, Dv = state.shape
    hb = min(_GATED_DELTA_HEADS, H)
    if H % hb:
        raise ValueError(f"gated_delta_step: {H} value heads do not divide "
                         f"into blocks of {hb}")
    f32 = jnp.float32
    wide = jnp.ones((B, H, Dv), f32)
    # a head's rows side by side: q and k, then v, exp(g) and beta
    qk = jnp.stack([q.astype(f32), k.astype(f32)], 2).reshape(B, H * 2, Dk)
    vgb = jnp.stack([v.astype(f32), jnp.exp(g.astype(f32))[..., None] * wide,
                     beta.astype(f32)[..., None] * wide], 2).reshape(
                         B, H * 3, Dv)
    return pl.pallas_call(
        functools.partial(_gated_delta_kernel, heads=hb),
        out_shape=(jax.ShapeDtypeStruct((B, H, Dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid=(B, H // hb),
        in_specs=[pl.BlockSpec((1, 2 * hb, Dk), lambda b, j: (b, j, 0)),
                  pl.BlockSpec((1, 3 * hb, Dv), lambda b, j: (b, j, 0)),
                  pl.BlockSpec((1, hb, Dk, Dv), lambda b, j: (b, j, 0, 0))],
        out_specs=(pl.BlockSpec((1, hb, Dv), lambda b, j: (b, j, 0)),
                   pl.BlockSpec((1, hb, Dk, Dv), lambda b, j: (b, j, 0, 0))),
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(),
        name="gated_delta_step",
    )(qk, vgb, state)
