"""Post-training int8 quantization for the inference stack (ISSUE 19).

The byte-diet argument (PR 2) applied to serving: decode is
bandwidth-bound — the param stream dominates a decode step and the KV
slab dominates the rest — so shipping int8 payloads with separately
stored scales cuts the bytes the step actually touches. Layout rules:

  * Weights: SYMMETRIC per-channel int8. Linear weights [in, out]
    scale per OUTPUT channel (axis 0 reduction → scale [1, out]);
    embedding-style tables [rows, d] scale per ROW (axis 1 reduction →
    scale [rows, 1]). Either way the scale is shaped for direct
    broadcast against the matmul/gather RESULT, so dequant commutes:
    ``(x @ q.astype(f32)) * scale`` — the fp32 weight copy is never
    materialised and accumulation happens in fp32.
  * KV cache: per-(k/v, row, position) scales — the reduction is over
    (heads, head_dim) only, IDENTICAL in the S=1 step and the chunked
    prefill forms, which is what makes quantized replay-resume
    bit-exact (see `models/transformer.py`).
  * fp8-ready: scales live in their own plane, never packed next to
    the int8 payload, so swapping the payload dtype is a local change.

Nothing here touches training; `generate()` stays fp32. The mode knob
lives in `stats._CONFIG["inference_quant"]` ("off" | "int8") so the
existing save/restore-eager-config fixtures cover it, and it joins
`export_cache.knob_fingerprint()` + `tuning.KNOBS` — flip ⇒ AOT miss,
and the autotuner scores it like any other HLO-shaping knob.
"""
from __future__ import annotations

import numpy as np

QMAX = 127.0          # symmetric int8: [-127, 127], -128 unused
_SCALE_TINY = 1e-30   # amax floor: all-zero channels quantize to 0


def mode() -> str:
    """Current inference quant mode: "off" or "int8"."""
    from . import stats

    return stats.get_config().get("inference_quant", "off")


def enabled() -> bool:
    return mode() == "int8"


# -- weight quantization (host side, numpy) ---------------------------

def quantize_weight(w, axis: int):
    """Symmetric per-channel int8: reduce |w| over `axis`, keepdims,
    so the returned scale broadcasts directly against either the
    weight or (for axis=0 on [in, out] linears) the matmul result.
    Returns (payload int8, scale float32)."""
    w = np.asarray(w, np.float32)
    amax = np.max(np.abs(w), axis=axis, keepdims=True)
    scale = np.maximum(amax, _SCALE_TINY) / QMAX
    q = np.clip(np.rint(w / scale), -QMAX, QMAX).astype(np.int8)
    return q, scale.astype(np.float32)


def dequantize_weight(q, scale):
    return np.asarray(q, np.float32) * np.asarray(scale, np.float32)


# -- KV-slab helpers --------------------------------------------------
#
# A layer of the decode slab holds POSITIONS LAST: [2, B, H, D, T]
# (keys at [0], values at [1]). The chip keeps the positions in the
# lanes whatever the program says (a 64-wide axis is never minor
# there), so this is the layout the attention products read and the
# one `cache_write` updates in place; any other is re-laid around
# every write (PERF.md, PR 28). A quantized slab is a per-layer list
# of (payload, scale) tuples in the same geometry:
#   payload int8  [2, B, H, D, T]
#   scale   f32   [2, B, T]      (reduced over H and D per position)
# Plain tuples, not a custom pytree class: jax.export serializes the
# builtin containers, so the AOT decode ladder works unchanged.
# `generate()`'s own stacked cache stays [L, 2, B, H, T, D], and so
# does the wire form of a migrated row ([L, 2, H, pos, D], below).

def is_quant_cache(cache) -> bool:
    """True when `cache` is a quantized per-layer slab (list of
    (payload, scale) tuples) rather than a plain array list."""
    return (bool(cache) and isinstance(cache[0], tuple)
            and len(cache[0]) == 2)


def cache_sig(cache):
    """Program-cache key fragment for a decode cache: shapes + dtype
    + quant marker. Replaces the bare `cache[0].dtype.name` idiom,
    which assumes array leaves."""
    if is_quant_cache(cache):
        return (tuple(tuple(p.shape) for p, _ in cache)
                + tuple(tuple(s.shape) for _, s in cache),
                "int8+scale")
    return (tuple(tuple(c.shape) for c in cache),
            np.dtype(cache[0].dtype).name)


def slab_payload(layer):
    """The [2, B, H, D, T] array of one layer, for either slab form."""
    return layer[0] if isinstance(layer, tuple) else layer


def slab_shape(slab):
    """[2, B, H, D, T] geometry of layer 0, for either slab form."""
    return tuple(slab_payload(slab[0]).shape)


def pad_slab_seq(slab, new_t):
    """Zero-pad the positions (the last axis of every array of either
    slab form) to `new_t` (the `_grow_slab` path). Stale-tail argument
    makes zeros exact."""
    import jax
    import jax.numpy as jnp

    def grown(a):
        return jnp.pad(a, ((0, 0),) * (a.ndim - 1)
                       + ((0, new_t - int(a.shape[-1])),))

    return jax.tree_util.tree_map(grown, list(slab))


def quantize_kv(kv, axes=(2, 4)):
    """In-graph per-position KV quantization: `kv` f32
    [2, B, H, S, D] → (payload int8 same shape, scale f32 [2, B, S]).
    The reduction extent (H, D) is the SAME whether S == 1 (decode
    step) or S == chunk (replay prefill), which is the bit-exactness
    lever: replaying a prefix chunk writes byte-identical payload and
    scale planes to the original per-step chain."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(kv), axis=axes)            # [2, B, S]
    scale = jnp.maximum(amax, _SCALE_TINY) / QMAX
    q = jnp.clip(jnp.round(kv / scale[:, :, None, :, None]),
                 -QMAX, QMAX).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(payload, scale):
    """[2,B,H,T,D] int8 + [2,B,T] f32 → f32 [2,B,H,T,D]: the layout of
    `generate()`'s own cache and of a chunk before it is stored (a
    slab layer, positions last, takes `dequantize_slab`)."""
    import jax.numpy as jnp

    return payload.astype(jnp.float32) * scale[:, :, None, :, None]


def dequantize_slab(payload, scale):
    """A slab layer: [2,B,H,D,T] int8 + [2,B,T] f32 → f32 [2,B,H,D,T];
    the same product per element as `dequantize_kv`."""
    import jax.numpy as jnp

    return payload.astype(jnp.float32) * scale[:, :, None, None, :]


# -- model-level quantized decode params ------------------------------

def quantize_decode_params(params):
    """Quantize a `_decode_params()` tree for the decode tier. Linear
    entries become length-3 tuples (payload, scale, bias) — tuple
    LENGTH is the dispatch, same idiom `_ln` uses for norm specs.
    Embedding-style tables ("embed", "pos", "head") become (payload,
    scale) pairs with per-row / per-column scales shaped for direct
    broadcast. Norm specs (arrays only, like every leaf of a jitted
    call's tree: `eps` is a constant of the traced program) pass
    through untouched."""
    def lin3(wb):
        w, b = wb
        q, s = quantize_weight(w, axis=0)    # per-output-channel
        return (q, s, b)

    blocks = []
    for blk in params["blocks"]:
        blocks.append({
            "ln1": blk["ln1"],
            "q": lin3(blk["q"]), "k": lin3(blk["k"]),
            "v": lin3(blk["v"]), "o": lin3(blk["o"]),
            "ln2": blk["ln2"],
            "fc1": lin3(blk["fc1"]), "fc2": lin3(blk["fc2"]),
        })
    return {
        "embed": quantize_weight(params["embed"], axis=1),  # per-row
        "pos": quantize_weight(params["pos"], axis=1),
        "blocks": blocks,
        "ln_f": params["ln_f"],
        "head": quantize_weight(params["head"], axis=0),    # per-col
    }


# -- forward-path param-stream quantization (arbitrary models) --------

_FWD_MIN_SIZE = 1024   # small leaves (LN gammas, biases) stay fp32


def forward_eligible(leaf) -> bool:
    """A forward param leaf rides int8 when it is a float matrix big
    enough for the byte-diet to matter."""
    a = np.asarray(leaf)
    return (a.ndim >= 2 and a.size >= _FWD_MIN_SIZE
            and np.issubdtype(a.dtype, np.floating))


def quantize_forward_leaf(leaf):
    """(payload int8, scale f32 broadcast-shaped) for a forward param
    leaf — per-channel over the LAST axis so each output column of a
    `x @ W` keeps its own scale; the shaped scale means the in-graph
    dequant needs no axis metadata."""
    a = np.asarray(leaf, np.float32)
    amax = np.max(np.abs(a), axis=-2, keepdims=True)
    scale = np.maximum(amax, _SCALE_TINY) / QMAX
    q = np.clip(np.rint(a / scale), -QMAX, QMAX).astype(np.int8)
    return q, scale.astype(np.float32)


# -- calibration ------------------------------------------------------

def calibrate(model, batch, *, seed: int = 0):
    """Sweep one seeded batch through the model in eval mode and
    record per-output activation absmax, accumulated at the BN
    statistics promotion floor (`set_bn_stats_dtype` idiom: never
    below fp32). Post-training symmetric weight quant doesn't strictly
    need activation ranges — scales come from the weights — but the
    sweep (a) validates the quantized forward against the fp32 one on
    the spot and (b) stores the ranges on the model for future
    activation-quant / fp8 work. Returns the range dict, also stored
    as `model._quant_calibration`."""
    from . import stats as stats_mod
    from . import tensor as tensor_mod

    floor = np.dtype(stats_mod.bn_stats_dtype())
    acc_dt = floor if floor.itemsize >= 4 else np.dtype(np.float32)
    was_training = getattr(model, "_training", False)
    try:
        model.eval()
    except Exception:
        pass
    try:
        out = model.forward(batch)
        arr = np.asarray(
            tensor_mod.to_numpy(out) if hasattr(out, "device")
            else out, acc_dt)
        ranges = {
            "seed": int(seed),
            "output_absmax": float(np.max(np.abs(arr))),
            "output_mean_abs": float(np.mean(np.abs(arr))),
            "accum_dtype": acc_dt.name,
        }
    finally:
        if was_training:
            try:
                model.train()
            except Exception:
                pass
    model._quant_calibration = ranges
    return ranges


# -- migration wire format --------------------------------------------
#
# ckpt["kv"]        numpy int8 [L, 2, H, pos, D]  (shape[3] == pos,
#                   same accessor as the fp32 rows — ~4x fewer bytes)
# ckpt["kv_scale"]  numpy f32  [L, 2, pos]
# fleet_proc.encode_tree ships numpy leaves natively, so the packed
# pair rides MIGRATE/RESUME frames without codec changes.

def rows_to_wire(slab, slot, pos):
    """One session's first `pos` positions out of every layer's
    [2, B, H, D, T] array (plain layer or payload) as one host array
    in the wire form [L, 2, H, pos, D]: transposed here, at the edge,
    so a frame reads the same whatever layout the slab has."""
    return np.ascontiguousarray(np.stack([
        np.asarray(slab_payload(c)[:, slot, :, :, :pos])
        for c in slab]).transpose(0, 1, 2, 4, 3))


def rows_from_wire(rows, seq):
    """The wire form [L, 2, H, pos, D] as the slab stores a row:
    [L, 2, H, D, seq], zeros past `pos`."""
    L, two, H, t, D = rows.shape
    out = np.zeros((L, two, H, D, seq), rows.dtype)
    out[..., :t] = rows.transpose(0, 1, 2, 4, 3)
    return out


def pack_slab_rows(slab, slot, pos):
    """Quantized counterpart of `export_slab_rows`: host-side gather
    of one session's live rows in PACKED form. Returns
    (payload int8 [L, 2, H, pos, D], scale f32 [L, 2, pos])."""
    sc = np.stack([np.asarray(s[:, slot, :pos]) for _, s in slab])
    return rows_to_wire(slab, slot, pos), sc


def stats_counters():
    """Process-wide quant counters (weights quantized, KV bytes moved
    packed) — debugging surface, not a gate."""
    global _COUNTERS
    return _COUNTERS


_COUNTERS = {"weights_quantized": 0, "packed_kv_exports": 0}
