"""Ring attention: exact attention over sequences sharded across chips.

The reference has no long-context machinery at all (SURVEY.md §5 —
max sequence is Char-RNN / BERT-base scale), but this framework treats
sequence/context parallelism as first-class. Design is the standard
TPU recipe (Liu et al. ring attention; blockwise-stable softmax):

  * the sequence dim of q, k, v is sharded over the mesh's "seq" axis;
  * each chip holds one q block and, over `seq` steps, streams every
    k/v block past it with `lax.ppermute` (neighbor exchange → the
    transfers ride ICI and overlap with the local block matmul);
  * softmax is accumulated online (running max m, normalizer l, output
    o), so the result is *exact* attention, not an approximation;
  * the whole loop is a `lax.scan` inside `shard_map`, so it is
    reverse-differentiable — autograd gets the backward pass via
    `jax.vjp` like every other op.

Complexity per chip: O(S_local · S_global · d), memory O(S_local²)
per block pair — sequences scale with the number of chips.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _neg_big(dtype):
    # A finite "minus infinity": keeps fully-masked rows NaN-free.
    return jnp.asarray(jnp.finfo(dtype).min / 2, dtype)


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          scale: float):
    """Per-chip body. q,k,v: [B, H, S_local, D] (this chip's shard)."""
    axis_size = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    dtype = q.dtype
    neg = _neg_big(dtype)

    m0 = jnp.full((B, H, Sq), neg, dtype)
    l0 = jnp.zeros((B, H, Sq), dtype)
    o0 = jnp.zeros_like(q)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    qpos = my * Sq + jnp.arange(Sq)

    def step(carry, i):
        o, m, l, kc, vc = carry
        # kc originated on chip (my - i) mod axis_size.
        src = (my - i) % axis_size
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kc) * scale
        if causal:
            kpos = src * Sk + jnp.arange(Sk)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vc)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (o, m_new, l, kc, vc), None

    (o, _, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v),
                                  jnp.arange(axis_size))
    return o / jnp.maximum(l, jnp.asarray(1e-30, dtype))[..., None]


def plain_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None, precision=None):
    """Single-device reference semantics (and the <2-way-SP fallback).
    q,k,v: [B, H, S, D]. `precision` is the einsums' MXU precision
    (`autograd.Attention` passes the framework's matmul policy; None
    is jax's default, one bf16 pass for float32 on the TPU)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=precision) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask, s, _neg_big(s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=precision)


def ring_attention(q, k, v, mesh: Mesh, *, axis_name: str = "seq",
                   causal: bool = True, scale: Optional[float] = None,
                   batch_axis: Optional[str] = "data",
                   head_axis: Optional[str] = "model"):
    """Exact attention with the sequence dim sharded over `axis_name`.

    q,k,v are *global* [B, H, S, D] arrays (GSPMD view); the per-chip
    partitioning is: batch over `batch_axis`, heads over `head_axis`,
    sequence over `axis_name` — any axis absent from the mesh degrades
    to replicated.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    names = mesh.axis_names

    def usable(ax, dim):  # same degrade-to-replicated rule as sharding.py
        return (ax in names and mesh.shape[ax] > 1
                and dim % mesh.shape[ax] == 0)

    B, H, S, _ = q.shape
    if not usable(axis_name, S):
        return plain_attention(q, k, v, causal=causal, scale=scale)
    ba = batch_axis if batch_axis and usable(batch_axis, B) else None
    ha = head_axis if head_axis and usable(head_axis, H) else None
    spec = P(ba, ha, axis_name, None)
    fn = shard_map(
        partial(_ring_attention_local, axis_name=axis_name,
                causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
