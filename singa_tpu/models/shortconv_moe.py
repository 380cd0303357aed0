"""A gated short-convolution / grouped-query-attention mixture-of-experts
LM (`model_type` `lfm2_moe`), served with every expert of a layer held.

Per layer l, kind(l) = `layer_types[l]` ("conv" or "full_attention");
the MLP is dense for l < `num_dense_layers`, routed after. No bias
anywhere.

    h += Op_l(RMSNorm(h));   h += MLP_l(RMSNorm(h))
    conv:  [b, c, x] = split3(n W_in)            each [S, d], in that order
           u_t = b_t * x_t
           z_t = sum_{j<L} w[j] * u_{t-(L-1)+j}  depthwise, causal, u_{<0} = 0
           Op  = (c_t * z_t) W_out
    attn:  q = n W_q -> [S,Hq,D];  k = n W_k, v = n W_v -> [S,Hkv,D]
           q, k = RMSNorm over each head's D dims (one gain [D] each),
           THEN rotary over all D dims (rotate-half);  s_ij = q_i.k_j /
           sqrt(D), j <= i;  Op = concat_heads(softmax(s) v) W_o
    dense: (silu(n W_g) * n W_u) W_d
    MoE:   `routed_experts`: sigmoid scores, top-k by score + bias,
           w_e = sig_e / (sum_S sig + router_sum_eps)
    logits = RMSNorm(h_last) E^T                 E the embedding (tied)

One functional stack (`_stack`) serves `forward()` in eval mode, the
cohort prefill and the fused decode step; there is no backward, so
`train_one_batch` raises. The cache is of two kinds in one slab. An
attention layer holds the context, keys and values alike [slots, Hkv,
D, T], positions last (with 64-wide heads a value block [T, D] would
half-fill the lanes, and XLA re-lays such a layer whole, in and out,
every step), climbing the sequence ladder. A convolution layer holds a
STATE, {"u": [slots, L-1, d]}: the last L-1 gated inputs u of the
slot's session, u_p in column p mod (L-1), rows of d as the step
multiplies them by the taps. A state does not grow with the rung and
nothing masks it: a step overwrites the one column that held the
oldest u (a select, in place in the donated slab; shifting the columns
instead makes XLA build the state anew and copy it back), and a
prefill writes ALL of it from the row's last L-1 REAL positions (zero
where the prompt is shorter), so a slot that takes a new session keeps
nothing of the last one.
"""
from __future__ import annotations

import numpy as np

from .. import tensor
from .drawn_lm import (RoutedDrawnLM, attend_cache, attend_prompts,
                       conv_columns, conv_prompt, conv_step, conv_tail,
                       conv_tail_positions, dense_mlp, put_rows, rope)

CONV, ATTN = "conv", "full_attention"


class ShortConvMoELM(RoutedDrawnLM):
    """Causal LM over int token ids [B, S] -> logits [B, S, vocab]."""

    _slab_words = "contexts and convolution states"
    _training_lacks = ("with no backward for the gated short convolution, "
                       "normed rotary heads or the routed experts, and no "
                       "optimizer state for the experts")

    def __init__(self, vocab_size: int, d_model: int = 2048,
                 num_heads: int = 32, kv_heads: int = 8, head_dim: int = 64,
                 conv_L: int = 3, rope_theta: float = 1e6,
                 layer_types=(CONV, CONV, ATTN, CONV),
                 num_dense_layers: int = 2, d_ff: int = 11776,
                 d_ff_expert: int = 1536, n_experts: int = 64,
                 experts_per_token: int = 4, held=(0, 64),
                 router_sum_eps: float = 1e-6,
                 norm_eps: float = 1e-5, max_len: int = 4096,
                 param_dtype: str = "float32", init_std: float = 0.02):
        super().__init__()
        layer_types = tuple(str(v) for v in layer_types)
        if set(layer_types) - {CONV, ATTN}:
            raise ValueError(f"layer_types {layer_types}: each is "
                             f"{CONV!r} or {ATTN!r}")
        if ATTN not in layer_types:
            raise ValueError("no full_attention layer: the slab's sequence "
                             "rung is an attention layer's")
        if num_heads % kv_heads:
            raise ValueError("query heads must divide by the key/value heads")
        if head_dim % 2 or conv_L < 2:
            raise ValueError(f"head_dim {head_dim} (even), conv_L {conv_L} "
                             "(at least 2)")
        self._init_drawn(vocab_size, max_len, norm_eps, param_dtype, init_std,
                         n_experts, experts_per_token, held)
        self.d_model, self.num_heads = int(d_model), int(num_heads)
        self.kv_heads, self.head_dim = int(kv_heads), int(head_dim)
        self.conv_L, self.rope_theta = int(conv_L), float(rope_theta)
        self.layer_types = layer_types
        self.num_dense_layers = int(num_dense_layers)
        self.d_ff, self.d_ff_expert = int(d_ff), int(d_ff_expert)
        self.router_sum_eps = float(router_sum_eps)

    def _routed(self, li):
        return li >= self.num_dense_layers

    def _param_table(self):
        """Every parameter: (dotted name under the model, shape, dtype,
        std of its normal draw or None, constant value or None)."""
        d, Hq, Hkv, D = (self.d_model, self.num_heads, self.kv_heads,
                         self.head_dim)
        pd, f32, std = self.param_dtype, np.dtype("float32"), self.init_std
        E, f = self.held[1], self.d_ff_expert
        out = [("embed.W", (self.vocab_size, d), pd, std, None)]
        for li, kind in enumerate(self.layer_types):
            pre = f"blocks.l{li}."
            out.append((pre + "ln1.gamma", (d,), f32, None, 1.0))
            if kind == CONV:
                out += [
                    (pre + "conv.W_in", (d, 3 * d), pd, std, None),
                    # std 0.3: taps of the size a trained filter has,
                    # so a test can tell a dropped or misplaced one
                    (pre + "conv.w", (self.conv_L, d), pd, 0.3, None),
                    (pre + "conv.W_out", (d, d), pd, std, None)]
            else:
                out += [
                    (pre + "attn.W_qkv", (d, (Hq + 2 * Hkv) * D), pd, std,
                     None),
                    (pre + "attn.q_norm", (D,), f32, None, 1.0),
                    (pre + "attn.k_norm", (D,), f32, None, 1.0),
                    (pre + "attn.W_o", (Hq * D, d), pd, std, None)]
            out.append((pre + "ln2.gamma", (d,), f32, None, 1.0))
            if self._routed(li):
                out += [
                    (pre + "moe.W_r", (d, self.n_experts), f32, std, None),
                    # not zero, so selection by sig + b differs from
                    # selection by sig
                    (pre + "moe.b", (self.n_experts,), f32, 0.1, None),
                    (pre + "moe.W_g", (E, d, f), pd, std, None),
                    (pre + "moe.W_u", (E, d, f), pd, std, None),
                    (pre + "moe.W_d", (E, f, d), pd, std, None)]
            else:
                out += [
                    (pre + "mlp.W_g", (d, self.d_ff), pd, std, None),
                    (pre + "mlp.W_u", (d, self.d_ff), pd, std, None),
                    (pre + "mlp.W_d", (self.d_ff, d), pd, std, None)]
        return out + [("ln_f.gamma", (d,), f32, None, 1.0)]

    def _tree(self, leaf):
        """The tree every program receives, from `leaf(dotted name)`."""
        blocks = []
        for li, kind in enumerate(self.layer_types):
            pre = f"blocks.l{li}."
            part, names = (("conv.", ("W_in", "w", "W_out"))
                           if kind == CONV else
                           ("attn.", ("W_qkv", "q_norm", "k_norm", "W_o")))
            out = {"ln1": leaf(pre + "ln1.gamma"),
                   "op": {n: leaf(pre + part + n) for n in names},
                   "ln2": leaf(pre + "ln2.gamma")}
            part, names = (("moe.", ("W_r", "b", "W_g", "W_u", "W_d"))
                           if self._routed(li)
                           else ("mlp.", ("W_g", "W_u", "W_d")))
            out["ffn"] = {n: leaf(pre + part + n) for n in names}
            blocks.append(out)
        return {"embed": leaf("embed.W"), "blocks": blocks,
                "ln_f": leaf("ln_f.gamma")}

    # -- what is not implemented, by mechanism -----------------------------
    def _shard_decode_params(self, params, mesh):
        raise NotImplementedError(
            "ShortConvMoELM: the tensor-parallel shard path is not "
            "implemented: a convolution state and 8 key/value heads have "
            "no sharding rule, and experts across chips need their "
            "exchange")

    # -- the mathematics ---------------------------------------------------
    def _stack(self, params, ids, pos, attend, convolve):
        """Embedding through the final norm for ids [B, S] at positions
        pos [B, S]. The caller's two callbacks keep what their cache
        keeps: `attend(li, q, k, v)` takes an attention layer's normed
        and rotated q [B,S,Hkv,G,D], k and v [B,S,Hkv,D] and returns
        [B,S,Hkv,G,D]; `convolve(li, u, w)` takes a convolution layer's
        gated input u [B,S,d] and taps w [L,d] (float32) and returns
        z [B,S,d] in float32. Returns (hidden [B,S,d], the routed
        layers' counters [3])."""
        import jax
        import jax.numpy as jnp

        prec = tensor.get_matmul_precision()
        B, S = ids.shape
        Hq, Hkv, D = self.num_heads, self.kv_heads, self.head_dim
        h = params["embed"][ids]
        counters = jnp.zeros(3, jnp.int32)
        for li, (kind, blk) in enumerate(zip(self.layer_types,
                                             params["blocks"])):
            op = blk["op"]
            if kind == CONV:
                with jax.named_scope("short_conv"):
                    x = self._rms(h, blk["ln1"])
                    b, c, x = jnp.split(
                        jnp.matmul(x, op["W_in"], precision=prec), 3, -1)
                    z = convolve(li, b * x, op["w"].astype(jnp.float32))
                    y = (c.astype(jnp.float32) * z).astype(h.dtype)
                    h = h + jnp.matmul(y, op["W_out"], precision=prec)
            else:
                with jax.named_scope("attn_full"):
                    x = self._rms(h, blk["ln1"])
                    q, k, v = jnp.split(
                        jnp.matmul(x, op["W_qkv"], precision=prec),
                        [Hq * D, (Hq + Hkv) * D], -1)
                    q = self._rms(q.reshape(B, S, Hq, D), op["q_norm"])
                    k = self._rms(k.reshape(B, S, Hkv, D), op["k_norm"])
                    q = rope(q, pos, self.rope_theta, D)
                    k = rope(k, pos, self.rope_theta, D)
                    a = attend(li, q.reshape(B, S, Hkv, Hq // Hkv, D), k,
                               v.reshape(B, S, Hkv, D))
                    h = h + jnp.matmul(a.reshape(B, S, Hq * D), op["W_o"],
                                       precision=prec)
            x = self._rms(h, blk["ln2"])
            ffn = blk["ffn"]
            if self._routed(li):
                y, counts = self._experts(ffn, x.reshape(B * S, -1), prec)
                h = h + y.reshape(B, S, -1)
                counters = counters + jnp.stack(
                    [counts.sum(), (counts > 0).sum(), counts.max()])
            else:
                h = h + dense_mlp(ffn, x, prec)
        return self._rms(h, params["ln_f"]), counters

    def _head(self, params, h):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("head"):
            # the embedding again, as stored [V, d] (tied); float32 on
            # the way out: the host takes its argmax over them, and
            # numpy has no fast path for bfloat16
            return jnp.einsum("...d,vd->...v", h, params["embed"],
                              precision=tensor.get_matmul_precision(),
                              preferred_element_type=jnp.float32)

    # -- eval forward --------------------------------------------------------
    def _eval_logits(self, params, ids):
        import jax.numpy as jnp

        B, S = ids.shape
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        prec = tensor.get_matmul_precision()
        h, _ = self._stack(
            params, ids, pos,
            lambda li, q, k, v: attend_prompts(q, k, v, prec),
            lambda li, u, w: conv_prompt(u, w))
        return self._head(params, h)

    # -- the slab: states beside contexts ----------------------------------
    def new_slab(self, params, slots, seq, device):
        """Per layer {"k", "v": [slots, Hkv, D, seq]} where it attends,
        {"u": [slots, L-1, d]} where it convolves."""
        import jax.numpy as jnp

        dtype = params["embed"].dtype
        Hkv, D = self.kv_heads, self.head_dim

        def zeros(*shape):
            return jnp.zeros(shape, dtype, device=device)

        return [{"u": zeros(slots, self.conv_L - 1, self.d_model)}
                if kind == CONV else
                {"k": zeros(slots, Hkv, D, seq), "v": zeros(slots, Hkv, D, seq)}
                for kind in self.layer_types]

    def grow_slab(self, slab, new_seq):
        """Only what holds the context grows; a state is left alone."""
        import jax.numpy as jnp

        def grown(c):
            more = new_seq - c["v"].shape[3]
            return {n: jnp.pad(a, ((0, 0),) * 3 + ((0, more),))
                    for n, a in c.items()}

        return [c if kind == CONV else grown(c)
                for kind, c in zip(self.layer_types, slab)]

    def slab_dims(self, slab):
        v = slab[self.layer_types.index(ATTN)]["v"]
        return int(v.shape[0]), int(v.shape[3])

    def slab_bytes(self, slab):
        out = {"context": 0, "state": 0}
        for kind, c in zip(self.layer_types, slab):
            out["state" if kind == CONV else "context"] += sum(
                a.size * a.dtype.itemsize for a in c.values())
        return out

    # -- the programs' step functions --------------------------------------
    def _slot_step(self, params, slab, tok, pos):
        """One fused decode step over every slot at per-row positions:
        an attention layer writes row b's key and value at pos[b], in
        place (`cache_write`), and attends positions 0..pos[b]; a
        convolution layer multiplies row b's state and its new u by
        the taps and puts u over the oldest column. Returns (logits
        [B, V], new slab, counters [3])."""
        import jax.numpy as jnp

        from ..ops.pallas_kernels import cache_write

        prec = tensor.get_matmul_precision()
        new = [None] * len(slab)

        def attend(li, q, k, v):
            c = slab[li]
            k_all = cache_write(c["k"], k[:, 0], pos, axis=3)
            v_all = cache_write(c["v"], v[:, 0], pos, axis=3)
            new[li] = {"k": k_all, "v": v_all}
            return attend_cache(q[:, 0], k_all, v_all, pos, prec,
                                values="bhdt")[:, None]

        tap = conv_columns(pos, self.conv_L)

        def convolve(li, u, w):
            z, state = conv_step(slab[li]["u"], u, w, tap)
            new[li] = {"u": state}
            return z

        h, counters = self._stack(params, tok[:, None], pos[:, None],
                                  attend, convolve)
        return self._head(params, h[:, 0]), new, counters

    def _prefill_rows(self, params, slab, ids, n_real, slots):
        """A cohort of bucket-padded prompts [Bp, Pb] through the
        stack, their state written into slab rows `slots` (a row whose
        slot is out of bounds writes nothing). A context takes
        positions 0..Pb-1 (the pad tail is hidden by the causal mask
        and overwritten before any query attends it); a convolution
        state takes u at the row's last L-1 REAL positions, n_real -
        (L-1) .. n_real - 1, each in its column p mod (L-1), zero
        where the prompt has no such position, whatever the bucket's
        length: the pad tail never lands there, nor anything of the
        slot's last session."""
        import jax.numpy as jnp

        prec = tensor.get_matmul_precision()
        Bp, Pb = ids.shape
        back = conv_tail_positions(n_real, self.conv_L)     # [Bp, L-1]
        new = [None] * len(slab)

        def attend(li, q, k, v):
            c = slab[li]
            new[li] = {
                n: put_rows(c[n], t.transpose(0, 2, 3, 1), slots)
                for n, t in (("k", k), ("v", v))}
            return attend_prompts(q, k, v, prec)

        def convolve(li, u, w):
            new[li] = {"u": put_rows(slab[li]["u"], conv_tail(u, back),
                                     slots)}
            return conv_prompt(u, w)

        pos = jnp.broadcast_to(jnp.arange(Pb), (Bp, Pb))
        h, _ = self._stack(params, ids, pos, attend, convolve)
        last_h = jnp.take_along_axis(
            h, (n_real - 1)[:, None, None], axis=1)[:, 0]
        return self._head(params, last_h), new


def create_model(vocab_size=256, **kwargs):
    return ShortConvMoELM(vocab_size, **kwargs)
