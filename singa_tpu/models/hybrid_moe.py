"""A hybrid window/full-attention mixture-of-experts LM, served as ONE
chip's share of an expert-parallel deployment.

Per layer l, kind(l) in {full, window} (`layer_pattern`: 0 full, 1
window) and a dense or routed MLP (`moe_layers`):

    x  = RMSNorm(h)
    q,k,v = split(x W_qkv)   q:[Hq,Dk]  k:[Hkv,Dk]  v:[Hkv,Dv]*value_scale
    q,k   = rope(first `rotary_dim` dims, theta by kind, rotate-half)
    s_ij  = q_i.k_j / sqrt(Dk)   j <= i (full) | i-window < j <= i (window)
    a_i   = sum_j softmax([s_i., sink_head])_j v_j   (sink column only in
            window layers; its weight is dropped)
    h    += concat_heads(a) W_o
    x  = RMSNorm(h)
    dense:  h += (silu(x W_g) * x W_u) W_d
    MoE:    sig = sigmoid(x W_r) in float32; S = top_k(sig + b);
            w_e = sig_e / sum_S sig;  h += sum_{e in S and held} w_e expert_e(x)
    logits = RMSNorm(h_last) W_head

`held = (first, count)` names the experts this chip holds of
`n_experts`: the router runs at full width, the layer computes its own
experts' part of the result for the tokens routed to them and drops no
token at any imbalance, and that PARTIAL result goes on to the next
layer. Nothing stands in for the absent chips or their exchange.

One functional stack (`_stack`) serves `forward()` in eval mode, the
cohort prefill and the fused decode step; there is no backward, so
`train_one_batch` raises. The cache is of two kinds in one slab: full
layers hold the context (T positions, climbing the sequence ladder),
window layers a ring of `window` positions (written at p mod window,
whatever the context). K and V have different head sizes and are
separate arrays: keys [slots, Hkv, Dk, T], a position a column, as the
score product reads them; values [slots, Hkv, T, Dv], a position a
row.
"""
from __future__ import annotations

import numpy as np

from .. import tensor
from .drawn_lm import (RoutedDrawnLM, attend_cache, attend_prompts, dense_mlp,
                       put_rows, rope, softmax_probs)

FULL, WINDOW = 0, 1


class HybridWindowMoELM(RoutedDrawnLM):
    """Causal LM over int token ids [B, S] -> logits [B, S, vocab]."""

    _slab_words = "rings and contexts"
    _training_lacks = ("with no backward for windowed attention with a "
                       "sink, rotary positions or the routed experts, and "
                       "no optimizer state for a share of the experts")

    def __init__(self, vocab_size: int, d_model: int = 4096,
                 num_heads: int = 64, head_dim: int = 192,
                 v_head_dim: int = 128, kv_heads_full: int = 4,
                 kv_heads_window: int = 8, window: int = 128,
                 rotary_dim: int = 64, rope_theta_full: float = 1e7,
                 rope_theta_window: float = 1e4,
                 value_scale: float = 0.707,
                 layer_pattern=(0, 1, 1, 1, 1, 1, 0),
                 moe_layers=(0, 1, 1, 1, 1, 1, 1), d_ff: int = 16384,
                 d_ff_expert: int = 2048, n_experts: int = 256,
                 experts_per_token: int = 8, held=(0, 16),
                 norm_eps: float = 1e-5, max_len: int = 4096,
                 param_dtype: str = "float32", init_std: float = 0.02):
        super().__init__()
        if len(layer_pattern) != len(moe_layers):
            raise ValueError("layer_pattern and moe_layers differ in length")
        if num_heads % kv_heads_full or num_heads % kv_heads_window:
            raise ValueError("query heads must divide by each kind's "
                             "key/value heads")
        if rotary_dim % 2 or rotary_dim > head_dim:
            raise ValueError(f"rotary_dim {rotary_dim} of head_dim {head_dim}")
        self._init_drawn(vocab_size, max_len, norm_eps, param_dtype, init_std,
                         n_experts, experts_per_token, held)
        self.d_model, self.num_heads = int(d_model), int(num_heads)
        self.head_dim, self.v_head_dim = int(head_dim), int(v_head_dim)
        self.kv_heads = {FULL: int(kv_heads_full),
                         WINDOW: int(kv_heads_window)}
        self.rope_theta = {FULL: float(rope_theta_full),
                           WINDOW: float(rope_theta_window)}
        self.window, self.rotary_dim = int(window), int(rotary_dim)
        self.value_scale = float(value_scale)
        self.layer_pattern = tuple(int(v) for v in layer_pattern)
        self.moe_layers = tuple(int(v) for v in moe_layers)
        self.d_ff, self.d_ff_expert = int(d_ff), int(d_ff_expert)

    def _param_table(self):
        """Every parameter: (dotted name under the model, shape, dtype,
        std of its normal draw or None, constant value or None)."""
        d, Hq, Dk, Dv = (self.d_model, self.num_heads, self.head_dim,
                         self.v_head_dim)
        pd, f32 = self.param_dtype, np.dtype("float32")
        E, f = self.held[1], self.d_ff_expert
        out = [("embed.W", (self.vocab_size, d), pd, self.init_std, None)]
        for li, kind in enumerate(self.layer_pattern):
            pre = f"blocks.l{li}."
            Hkv = self.kv_heads[kind]
            out += [
                (pre + "ln1.gamma", (d,), f32, None, 1.0),
                (pre + "attn.W_qkv", (d, Hq * Dk + Hkv * (Dk + Dv)), pd,
                 self.init_std, None),
                (pre + "attn.W_o", (Hq * Dv, d), pd, self.init_std, None)]
            if kind == WINDOW:
                # not zero, so a test can tell a dropped sink
                out.append((pre + "attn.sink", (Hq,), f32, 1.0, None))
            out.append((pre + "ln2.gamma", (d,), f32, None, 1.0))
            if self.moe_layers[li]:
                out += [
                    (pre + "moe.W_r", (d, self.n_experts), f32,
                     self.init_std, None),
                    # not zero, so selection by sig + b differs from
                    # selection by sig
                    (pre + "moe.b", (self.n_experts,), f32, 0.1, None),
                    (pre + "moe.W_g", (E, d, f), pd, self.init_std, None),
                    (pre + "moe.W_u", (E, d, f), pd, self.init_std, None),
                    (pre + "moe.W_d", (E, f, d), pd, self.init_std, None)]
            else:
                out += [
                    (pre + "mlp.W_g", (d, self.d_ff), pd, self.init_std,
                     None),
                    (pre + "mlp.W_u", (d, self.d_ff), pd, self.init_std,
                     None),
                    (pre + "mlp.W_d", (self.d_ff, d), pd, self.init_std,
                     None)]
        return out + [
            ("ln_f.gamma", (d,), f32, None, 1.0),
            ("head.W", (d, self.vocab_size), pd, self.init_std, None)]

    def _tree(self, leaf):
        """The tree every program receives, from `leaf(dotted name)`."""
        blocks = []
        for li, kind in enumerate(self.layer_pattern):
            pre = f"blocks.l{li}."
            out = {"ln1": leaf(pre + "ln1.gamma"),
                   "qkv": leaf(pre + "attn.W_qkv"),
                   "o": leaf(pre + "attn.W_o"),
                   "ln2": leaf(pre + "ln2.gamma")}
            if kind == WINDOW:
                out["sink"] = leaf(pre + "attn.sink")
            part, names = (("moe.", ("W_r", "b", "W_g", "W_u", "W_d"))
                           if self.moe_layers[li]
                           else ("mlp.", ("W_g", "W_u", "W_d")))
            out["ffn"] = {n: leaf(pre + part + n) for n in names}
            blocks.append(out)
        return {"embed": leaf("embed.W"), "blocks": blocks,
                "ln_f": leaf("ln_f.gamma"), "head": leaf("head.W")}

    # -- what is not implemented, by mechanism -----------------------------
    def _shard_decode_params(self, params, mesh):
        raise NotImplementedError(
            "HybridWindowMoELM: the tensor-parallel shard path is not "
            "implemented: 4 key/value heads do not divide over a mesh, "
            "and experts across chips need their exchange")

    # -- the mathematics ---------------------------------------------------
    def _attend_self(self, kind, q, k, v, sink, prec):
        """Causal self-attention over the S tokens of a prompt at
        positions 0..S-1: q [B,S,Hkv,G,Dk], k [B,S,Hkv,Dk],
        v [B,S,Hkv,Dv] -> [B,S,Hkv,G,Dv]. Full layers a prompt at a
        time (`attend_prompts`); window layers by chunks of `window`
        queries over their own and the previous chunk's keys: no
        [S, S] score matrix is ever whole."""
        import jax.numpy as jnp

        if kind == FULL:
            return attend_prompts(q, k, v, prec)
        B, S, Hkv, G, Dk = q.shape
        scale = 1.0 / float(np.sqrt(Dk))
        W = self.window
        n = -(-S // W)
        pad = n * W - S

        def chunks(t):  # [B,S,...] -> [B,n,W,...]
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            return t.reshape(B, n, W, *t.shape[2:])

        def with_prev(t):  # [B,n,W,...] -> [B,n,2W,...]
            prev = jnp.pad(t[:, :-1], ((0, 0), (1, 0))
                           + ((0, 0),) * (t.ndim - 2))
            return jnp.concatenate([prev, t], 2)

        qc, kc, vc = chunks(q), with_prev(chunks(k)), with_prev(chunks(v))
        s = jnp.einsum("bcqhgd,bckhd->bchgqk", qc, kc, precision=prec,
                       preferred_element_type=jnp.float32) * scale
        iq, m = jnp.arange(W)[:, None], jnp.arange(2 * W)[None, :]
        band = (m > iq) & (m <= iq + W)             # i-W < j <= i
        real = (jnp.arange(n)[:, None, None] > 0) | (m >= W)[None]
        mask = (band[None] & real)[None, :, None, None]
        p = softmax_probs(s, mask, sink[None, None, :, :, None, None]
                          ).astype(v.dtype)
        out = jnp.einsum("bchgqk,bckhd->bcqhgd", p, vc, precision=prec)
        return out.reshape(B, n * W, Hkv, G, -1)[:, :S]

    def _stack(self, params, ids, pos, attend):
        """Embedding through the final norm for ids [B, S] at positions
        pos [B, S]. `attend(li, kind, q, k, v, sink)` is the caller's:
        it takes this layer's rotated q [B,S,Hkv,G,Dk], k [B,S,Hkv,Dk]
        and scaled v [B,S,Hkv,Dv], keeps what its cache keeps, and
        returns [B,S,Hkv,G,Dv]. Returns (hidden [B,S,d], the routed
        layers' counters [3])."""
        import jax
        import jax.numpy as jnp

        prec = tensor.get_matmul_precision()
        B, S = ids.shape
        Hq, Dk, Dv = self.num_heads, self.head_dim, self.v_head_dim
        h = params["embed"][ids]
        counters = jnp.zeros(3, jnp.int32)
        for li, (kind, blk) in enumerate(zip(self.layer_pattern,
                                             params["blocks"])):
            Hkv = self.kv_heads[kind]
            scope = "attn_full" if kind == FULL else "attn_window"
            with jax.named_scope(scope):
                x = self._rms(h, blk["ln1"])
                qkv = jnp.matmul(x, blk["qkv"], precision=prec)
                q, k, v = jnp.split(
                    qkv, [Hq * Dk, Hq * Dk + Hkv * Dk], -1)
                theta = self.rope_theta[kind]
                q = rope(q.reshape(B, S, Hq, Dk), pos, theta, self.rotary_dim)
                k = rope(k.reshape(B, S, Hkv, Dk), pos, theta,
                         self.rotary_dim)
                v = v.reshape(B, S, Hkv, Dv) * jnp.asarray(
                    self.value_scale, v.dtype)
                sink = (blk["sink"].reshape(Hkv, Hq // Hkv)
                        if kind == WINDOW else None)
                a = attend(li, kind, q.reshape(B, S, Hkv, Hq // Hkv, Dk),
                           k, v, sink)
                h = h + jnp.matmul(a.reshape(B, S, Hq * Dv), blk["o"],
                                   precision=prec)
            x = self._rms(h, blk["ln2"])
            ffn = blk["ffn"]
            if self.moe_layers[li]:
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
            # float32 on the way out: the host takes its argmax over
            # them, and numpy has no fast path for bfloat16
            return jnp.matmul(h, params["head"],
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
            lambda li, kind, q, k, v, sink: self._attend_self(
                kind, q, k, v, sink, prec))
        return self._head(params, h)

    # -- the slab: rings beside contexts ---------------------------------
    def new_slab(self, params, slots, seq, device):
        """Per layer {"k": [slots, Hkv, Dk, T], "v": [slots, Hkv, T,
        Dv]} with T = `seq` where the layer holds the context and
        `window` where it holds a ring."""
        import jax.numpy as jnp

        dtype = params["embed"].dtype
        out = []
        for kind in self.layer_pattern:
            T = seq if kind == FULL else self.window
            Hkv = self.kv_heads[kind]
            out.append({
                "k": jnp.zeros((slots, Hkv, self.head_dim, T), dtype,
                               device=device),
                "v": jnp.zeros((slots, Hkv, T, self.v_head_dim), dtype,
                               device=device)})
        return out

    def grow_slab(self, slab, new_seq):
        """Only what holds the context grows; a ring is left alone."""
        import jax.numpy as jnp

        def grown(c):
            more = new_seq - c["v"].shape[2]
            return {"k": jnp.pad(c["k"], ((0, 0),) * 3 + ((0, more),)),
                    "v": jnp.pad(c["v"], ((0, 0), (0, 0), (0, more),
                                          (0, 0)))}

        return [grown(c) if kind == FULL else c
                for kind, c in zip(self.layer_pattern, slab)]

    def slab_dims(self, slab):
        v = slab[self.layer_pattern.index(FULL)]["v"]
        return int(v.shape[0]), int(v.shape[2])

    def slab_bytes(self, slab):
        out = {"ring": 0, "context": 0}
        for kind, c in zip(self.layer_pattern, slab):
            out["context" if kind == FULL else "ring"] += sum(
                a.size * a.dtype.itemsize for a in c.values())
        return out

    # -- the programs' step functions --------------------------------------
    def _slot_step(self, params, slab, tok, pos):
        """One fused decode step over every slot at per-row positions:
        row b writes its key and value at pos[b] (context) or pos[b]
        mod window (ring), in place, and attends what its layer's kind
        allows. Returns (logits [B, V], new slab, counters [3])."""
        from ..ops.pallas_kernels import cache_write

        prec = tensor.get_matmul_precision()
        new = []

        def attend(li, kind, q, k, v, sink):
            c = slab[li]
            at = pos if kind == FULL else pos % self.window
            k_all = cache_write(c["k"], k[:, 0], at, axis=3)
            v_all = cache_write(c["v"], v[:, 0], at, axis=2)
            new.append({"k": k_all, "v": v_all})
            return attend_cache(q[:, 0], k_all, v_all, pos, prec,
                                ring=kind == WINDOW, sink=sink)[:, None]

        h, counters = self._stack(params, tok[:, None], pos[:, None],
                                  attend)
        return self._head(params, h[:, 0]), new, counters

    def _prefill_rows(self, params, slab, ids, n_real, slots):
        """A cohort of bucket-padded prompts [Bp, Pb] through the
        stack, their state written into slab rows `slots` (a row whose
        slot is out of bounds writes nothing). A context takes
        positions 0..Pb-1 (the pad tail is hidden by the causal mask
        and overwritten before any query attends it); a ring takes
        each row's last min(n_real, window) REAL tokens, position j at
        j mod window: the pad tail never lands there."""
        import jax.numpy as jnp

        prec = tensor.get_matmul_precision()
        Bp, Pb = ids.shape
        W = self.window
        # ring slot r <- the last real position congruent to r (any
        # position where there is none: no query attends that slot
        # before a decode step has written it)
        r = jnp.arange(W)[None, :]
        last = n_real[:, None] - 1
        src = jnp.clip(last - (last - r) % W, 0, Pb - 1)      # [Bp,W]
        new = []

        def attend(li, kind, q, k, v, sink):
            c = slab[li]
            if kind == FULL:
                kr, vr = k, v
            else:
                kr = jnp.take_along_axis(k, src[:, :, None, None], 1)
                vr = jnp.take_along_axis(v, src[:, :, None, None], 1)
            new.append({"k": put_rows(c["k"], kr.transpose(0, 2, 3, 1), slots),
                        "v": put_rows(c["v"], vr.transpose(0, 2, 1, 3), slots)})
            return self._attend_self(kind, q, k, v, sink, prec)

        pos = jnp.broadcast_to(jnp.arange(Pb), (Bp, Pb))
        h, _ = self._stack(params, ids, pos, attend)
        last_h = jnp.take_along_axis(
            h, (n_real - 1)[:, None, None], axis=1)[:, 0]
        return self._head(params, last_h), new


def create_model(vocab_size=256, **kwargs):
    return HybridWindowMoELM(vocab_size, **kwargs)
