"""What the decode-tier models served at published widths share beside
`DecodeLM`'s programs: parameters drawn ON THE DEVICE from the seed in
their stored dtype (billions of them cannot be drawn leaf by leaf on
the host in float32), one functional stack outside autograd (no
backward, so `train_one_batch` raises), RMSNorm, rotary positions, a
softmax with an optional sink column, causal attention over a prompt
and over a cached context, and rows scattered into slab slots.

A subclass gives `_param_table()` (every parameter by dotted name),
`_tree(leaf)` (the tree its programs receive), `_eval_logits(params,
ids)` (the stack with no cache) and its slab; `_slab_words` says what
the slab holds, for the messages of what is not implemented.

`DrawnDecodeLM` is what EVERY such model shares (the draw, the norm,
`forward`, the not-implemented messages); `RoutedDrawnLM` adds what
the routed models share (the held range of experts, the one routed
layer with its constants, the three `moe_*` step counters). A dense
model (`chunked_attn.py`) carries none of that.
"""
from __future__ import annotations

import functools

import numpy as np

from .. import layer, tensor
from .decode_lm import DecodeLM
from .routed_experts import DENSE_ROWS, routed_experts


class _Params(layer.Layer):
    """Named parameters (and sublayers) with no mathematics of their
    own: `get_states()` names the arrays for a reference to take."""


@functools.lru_cache(maxsize=None)
def _drawer(shape, dtype, std):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key: (jax.random.normal(key, shape, jnp.float32)
                                * std).astype(dtype))


def rope(x, pos, theta, rotary_dim):
    """Rotate-half pairing over the first `rotary_dim` dims of
    x [..., S, H, D] at positions pos [..., S]; the rest pass."""
    import jax.numpy as jnp

    R = rotary_dim
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = pos[..., None].astype(jnp.float32) * inv      # [..., S, R/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1 = x[..., :R // 2].astype(jnp.float32)
    x2 = x[..., R // 2:R].astype(jnp.float32)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., R:]], -1)


def softmax_probs(s, mask, sink):
    """softmax over the keys of s [..., q, k] (float32) where `mask`
    allows them, with one more column `sink` [...,1,1] in the
    denominator whose weight is dropped."""
    import jax.numpy as jnp

    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, -1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink)
    e = jnp.where(mask, jnp.exp(s - m), 0.0)
    den = jnp.sum(e, -1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sink - m)
    return e / den


def attend_prompts(q, k, v, prec):
    """Causal self-attention over the S tokens of each prompt at
    positions 0..S-1: q [B,S,Hkv,G,Dk], k [B,S,Hkv,Dk], v [B,S,Hkv,Dv]
    -> [B,S,Hkv,G,Dv]. A prompt at a time, by chunks of queries over
    the keys up to the chunk's last query: no [S, S] score matrix is
    ever whole."""
    import jax.numpy as jnp
    from jax import lax

    S, Dk = q.shape[1], q.shape[-1]
    scale = 1.0 / float(np.sqrt(Dk))
    C = 256 if S > 256 and S % 256 == 0 else S

    def prompt(qkv):   # [S,Hkv,G,Dk], [S,Hkv,Dk], [S,Hkv,Dv]
        qr, kr, vr = qkv
        out = []
        for lo in range(0, S, C):
            hi = lo + C    # static: no key lies past the chunk
            s = jnp.einsum("qhgd,khd->hgqk", qr[lo:hi], kr[:hi],
                           precision=prec,
                           preferred_element_type=jnp.float32) * scale
            mask = (jnp.arange(hi)[None, :]
                    <= jnp.arange(lo, hi)[:, None])
            p = softmax_probs(s, mask, None).astype(vr.dtype)
            out.append(jnp.einsum("hgqk,khd->qhgd", p, vr[:hi],
                                  precision=prec))
        return jnp.concatenate(out, 0)

    return lax.map(prompt, (q, k, v))


def attend_cache(q, k_all, v_all, pos, prec, ring=False, sink=None,
                 values="bhtd"):
    """One query a row against its cache: q [B,Hkv,G,Dk], k_all
    [B,Hkv,Dk,T], v_all [B,Hkv,T,Dv] (or [B,Hkv,Dv,T] with `values`
    "bhdt": positions last, as the keys), row b at position pos[b]
    (already written). A context holds position j at j; a ring
    (`ring`, with its `sink` [Hkv,G]) holds the last T positions at
    j mod T."""
    import jax.numpy as jnp

    T = k_all.shape[3]
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
    s = jnp.einsum("bhgd,bhdt->bhgt", q, k_all, precision=prec,
                   preferred_element_type=jnp.float32) * scale
    j = jnp.arange(T)[None, :]
    mask = j <= pos[:, None]
    if ring:
        mask = mask | (pos[:, None] >= T - 1)
        sink = sink[None, :, :, None]
    p = softmax_probs(s, mask[:, None, None, :], sink).astype(v_all.dtype)
    return jnp.einsum(f"bhgt,{values}->bhgd", p, v_all, precision=prec)


def dense_mlp(ffn, x, prec):
    """(silu(x W_g) * x W_u) W_d."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("dense_mlp"):
        g = jnp.matmul(x, ffn["W_g"], precision=prec)
        u = jnp.matmul(x, ffn["W_u"], precision=prec)
        return jnp.matmul(jax.nn.silu(g) * u, ffn["W_d"], precision=prec)


def put_rows(cache, rows, slots):
    """rows [Bp, ...] over the head of cache rows `slots`, a row at a
    time and in place; an out-of-bounds slot keeps what the (clamped)
    row held."""
    import jax.numpy as jnp
    from jax import lax

    for b in range(rows.shape[0]):
        start = (slots[b],) + (0,) * (cache.ndim - 1)
        old = lax.dynamic_slice(cache, start, (1,) + rows.shape[1:])
        row = jnp.where(slots[b] < cache.shape[0], rows[b:b + 1], old)
        cache = lax.dynamic_update_slice(cache, row, start)
    return cache


class DrawnDecodeLM(DecodeLM):
    """Base of every decode-tier model whose parameters are drawn on
    the device: `RoutedDrawnLM`'s two and `ChunkedAttnLM`."""

    # the subclass's: what its slab holds, what a training path lacks
    # ("with no backward for ..., and no optimizer state for ..."), and
    # why it has no sharded path
    _slab_words = ""
    _training_lacks = ""
    _one_chip_holds = ""

    def _init_drawn(self, vocab_size, max_len, norm_eps, param_dtype,
                    init_std):
        import jax.numpy as jnp

        self.vocab_size, self.max_len = int(vocab_size), int(max_len)
        self.norm_eps = float(norm_eps)
        if param_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"param_dtype {param_dtype!r}: float32 or "
                             "bfloat16")
        self.param_dtype = jnp.dtype(param_dtype)
        self.init_std = float(init_std)

    # -- parameters, on the device from the seed ---------------------------
    def compile(self, inputs, is_train: bool = False,
                use_graph: bool = False, **kwargs):
        """Draw the parameters on `inputs[0]`'s device in their stored
        dtype and arm eval mode; there is nothing to trace."""
        if is_train:
            raise NotImplementedError(self._no_training())
        if kwargs.get("mesh") is not None or kwargs.get("plan") is not None:
            raise NotImplementedError(
                f"{type(self).__name__}: no sharded (mesh / ParallelPlan) "
                f"path; one chip holds {self._one_chip_holds}")
        from ..device import get_default_device

        dev = inputs[0].device if inputs else get_default_device()
        if not self.param_tensors():
            self._draw_params(dev)
        super().compile([], is_train=False, use_graph=False)

    def _draw_params(self, dev):
        """`_param_table()`'s rows (dotted name under the model, shape,
        dtype, std of its normal draw or None, constant value or None),
        each registered under its name."""
        import jax
        import jax.numpy as jnp

        base = dev.next_key()
        for n, (name, shape, dtype, std, value) in enumerate(
                self._param_table()):
            if value is not None:
                arr = jnp.full(shape, value, dtype, device=dev.jax_device)
            else:
                arr = _drawer(tuple(shape), np.dtype(dtype), float(std))(
                    jax.random.fold_in(base, n))
            *path, attr = name.split(".")
            holder = self
            for part in path:
                if not hasattr(holder, part):
                    setattr(holder, part, _Params())
                holder = getattr(holder, part)
            holder.register_param(attr, tensor.from_raw(arr, dev))

    def _decode_params(self):
        """Device arrays only: the parameters as they are stored."""
        return self._tree(lambda name: functools.reduce(
            getattr, name.split("."), self).data)

    def _norm_eps(self):
        return (self.norm_eps,)

    # -- what is not implemented, by mechanism -----------------------------
    def _no_training(self):
        return (f"{type(self).__name__} has no training path: its stack "
                f"is one jax function outside autograd, {self._training_lacks}")

    def train_one_batch(self, x, y):
        raise NotImplementedError(self._no_training())

    def _decode_params_quant(self):
        raise NotImplementedError(
            f"{type(self).__name__}: the int8 decode tier (quantized "
            "parameters and an int8 slab, device.set_inference_quant) "
            f"is not implemented for a slab of {self._slab_words}")

    def export_slab_rows(self, slab, slot, pos):
        raise NotImplementedError(
            f"{type(self).__name__}: KV export (live migration of a "
            "session's cache rows) is not implemented for a slab of "
            f"{self._slab_words}")

    def import_slab_rows(self, slab, slot, rows):
        raise NotImplementedError(
            f"{type(self).__name__}: KV import (resume from exported "
            f"cache rows) is not implemented for a slab of "
            f"{self._slab_words}; a resumed session replays its ledger")

    # -- the slab every such model states: a dict of arrays a layer --------
    @staticmethod
    def _slab_sig(slab):
        return (tuple(tuple(tuple(a.shape) for a in c.values())
                      for c in slab), next(iter(slab[0].values())).dtype.name)

    @staticmethod
    def _slab_extra(slab):
        return [[list(a.shape) for a in c.values()] for c in slab]

    # -- the mathematics every such stack has ------------------------------
    def _rms(self, h, gamma):
        import jax.numpy as jnp
        from jax import lax

        hf = h.astype(jnp.float32)
        y = hf * lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True)
                           + self.norm_eps) * gamma
        return y.astype(h.dtype)

    def forward(self, x):
        """Logits [B, S, vocab] of ids [B, S]: the same stack with no
        cache. Eval only; nothing is recorded for a backward."""
        import jax

        cache = self._program_cache()
        key_ = ("forward", self._trace_key())
        fn = cache.get(key_)
        if fn is None:
            fn = cache[key_] = jax.jit(self._eval_logits)
        return tensor.from_raw(fn(self._decode_params(), x.data), x.device)


class RoutedDrawnLM(DrawnDecodeLM):
    """Base of `HybridWindowMoELM`, `ShortConvMoELM` and
    `BlockSparseMoELM`: a drawn model with routed layers, of which this
    chip holds a range of experts."""

    # a run-ahead block is its steps in a row, not a loop: around a
    # loop XLA re-lays every held expert's gate and up matrices out
    # (12 copies of 0.83 ms a block on the chip, which the single step
    # reads as stored at the same speed) and carries a second slab
    # among its temporaries (3.4 GB at the served size)
    scan_unroll = True
    step_counter_names = ("moe_assignments_local", "moe_experts_touched",
                          "moe_expert_load_max")
    # `routed_experts`'s: one constant for every model that calls it,
    # and what an architecture may set: the number in its normalising
    # sum, its experts' activation (None: silu(g) * u) and the scale
    # of their part (`routed_scaling_factor`)
    dense_rows = DENSE_ROWS
    router_sum_eps = 0.0
    expert_act = None
    routed_scale = 1.0
    _one_chip_holds = "its share of the experts"

    def _init_drawn(self, vocab_size, max_len, norm_eps, param_dtype,
                    init_std, n_experts, experts_per_token, held):
        first, count = (int(v) for v in held)
        if not (0 <= first and count >= 1
                and first + count <= n_experts):
            raise ValueError(f"held {held} is no range of {n_experts} experts")
        self.n_experts = int(n_experts)
        self.experts_per_token = int(experts_per_token)
        self.held = (first, count)
        super()._init_drawn(vocab_size, max_len, norm_eps, param_dtype,
                            init_std)

    def _trace_key(self):
        return super()._trace_key() + (self.dense_rows,)

    def _experts(self, ffn, x, prec):
        """The held experts' part of a routed layer for x [N, d], and
        the held experts' assignment counts [count]: the decode tier's
        one routed layer, with this architecture's numbers."""
        return routed_experts(ffn, x, prec, held=self.held,
                              experts_per_token=self.experts_per_token,
                              dense_rows=self.dense_rows,
                              sum_eps=self.router_sum_eps,
                              act=self.expert_act, scale=self.routed_scale)
