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

import functools

import numpy as np

from .. import layer, tensor
from .decode_lm import DecodeLM

FULL, WINDOW = 0, 1


class _Params(layer.Layer):
    """Named parameters (and sublayers) with no mathematics of their
    own: `get_states()` names the arrays for a reference to take."""


@functools.lru_cache(maxsize=None)
def _drawer(shape, dtype, std):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key: (jax.random.normal(key, shape, jnp.float32)
                                * std).astype(dtype))


class HybridWindowMoELM(DecodeLM):
    """Causal LM over int token ids [B, S] -> logits [B, S, vocab]."""

    # a run-ahead block is its steps in a row, not a loop: around a
    # loop XLA re-lays every held expert's gate and up matrices out
    # (12 copies of 0.83 ms a block on the chip, which the single step
    # reads as stored at the same speed) and carries a second slab
    # among its temporaries (3.4 GB at the served size)
    scan_unroll = True
    step_counter_names = ("moe_assignments_local", "moe_experts_touched",
                          "moe_expert_load_max")
    # rows up to which every held expert runs over every row (one
    # dense product, the weights' bytes bound it); above, assignments
    # are sorted by expert and multiplied group by group
    dense_rows = 256

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
        first, count = (int(v) for v in held)
        if not (0 <= first and count >= 1
                and first + count <= n_experts):
            raise ValueError(f"held {held} is no range of {n_experts} experts")
        if num_heads % kv_heads_full or num_heads % kv_heads_window:
            raise ValueError("query heads must divide by each kind's "
                             "key/value heads")
        if rotary_dim % 2 or rotary_dim > head_dim:
            raise ValueError(f"rotary_dim {rotary_dim} of head_dim {head_dim}")
        self.vocab_size, self.max_len = int(vocab_size), int(max_len)
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
        self.n_experts = int(n_experts)
        self.experts_per_token = int(experts_per_token)
        self.held = (first, count)
        self.norm_eps = float(norm_eps)
        if param_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"param_dtype {param_dtype!r}: float32 or "
                             "bfloat16")
        import jax.numpy as jnp

        self.param_dtype = jnp.dtype(param_dtype)
        self.init_std = float(init_std)

    # -- parameters, on the device from the seed ---------------------------
    def compile(self, inputs, is_train: bool = False,
                use_graph: bool = False, **kwargs):
        """Draw the parameters on `inputs[0]`'s device in their stored
        dtype (3.4 G of them at the published widths cannot be drawn
        leaf by leaf on the host in float32) and arm eval mode; there
        is nothing to trace."""
        if is_train:
            raise NotImplementedError(self._no_training())
        if kwargs.get("mesh") is not None or kwargs.get("plan") is not None:
            raise NotImplementedError(
                "HybridWindowMoELM: no sharded (mesh / ParallelPlan) "
                "path; one chip holds its share of the experts")
        from ..device import get_default_device

        dev = inputs[0].device if inputs else get_default_device()
        if not self.param_tensors():
            self._draw_params(dev)
        super().compile([], is_train=False, use_graph=False)

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

    def _draw_params(self, dev):
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

    def _decode_params(self):
        """Device arrays only: the parameters as they are stored."""
        return self._tree(lambda name: functools.reduce(
            getattr, name.split("."), self).data)

    def _norm_eps(self):
        return (self.norm_eps,)

    def _trace_key(self):
        return super()._trace_key() + (self.dense_rows,)

    # -- what is not implemented, by mechanism -----------------------------
    @staticmethod
    def _no_training():
        return ("HybridWindowMoELM has no training path: its stack is "
                "one jax function outside autograd, with no backward "
                "for windowed attention with a sink, rotary positions "
                "or the routed experts, and no optimizer state for a "
                "share of the experts")

    def train_one_batch(self, x, y):
        raise NotImplementedError(self._no_training())

    def _decode_params_quant(self):
        raise NotImplementedError(
            "HybridWindowMoELM: the int8 decode tier (quantized "
            "parameters and an int8 slab, device.set_inference_quant) "
            "is not implemented for a slab of rings and contexts")

    def export_slab_rows(self, slab, slot, pos):
        raise NotImplementedError(
            "HybridWindowMoELM: KV export (live migration of a "
            "session's cache rows) is not implemented for a slab of "
            "rings and contexts")

    def import_slab_rows(self, slab, slot, rows):
        raise NotImplementedError(
            "HybridWindowMoELM: KV import (resume from exported cache "
            "rows) is not implemented for a slab of rings and contexts; "
            "a resumed session replays its ledger")

    def _shard_decode_params(self, params, mesh):
        raise NotImplementedError(
            "HybridWindowMoELM: the tensor-parallel shard path is not "
            "implemented: 4 key/value heads do not divide over a mesh, "
            "and experts across chips need their exchange")

    # -- the mathematics ---------------------------------------------------
    def _rms(self, h, gamma):
        import jax.numpy as jnp
        from jax import lax

        hf = h.astype(jnp.float32)
        y = hf * lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True)
                           + self.norm_eps) * gamma
        return y.astype(h.dtype)

    def _rope(self, x, pos, kind):
        """Rotate-half pairing over the first `rotary_dim` dims of
        x [..., S, H, D] at positions pos [..., S]; the rest pass."""
        import jax.numpy as jnp

        R = self.rotary_dim
        inv = self.rope_theta[kind] ** (
            -jnp.arange(0, R, 2, dtype=jnp.float32) / R)
        ang = pos[..., None].astype(jnp.float32) * inv      # [..., S, R/2]
        cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
        x1 = x[..., :R // 2].astype(jnp.float32)
        x2 = x[..., R // 2:R].astype(jnp.float32)
        rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
        return jnp.concatenate([rot.astype(x.dtype), x[..., R:]], -1)

    @staticmethod
    def _probs(s, mask, sink):
        """softmax over the keys of s [..., q, k] (float32) where
        `mask` allows them, with one more column `sink` [...,1,1] in
        the denominator whose weight is dropped."""
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

    def _attend_self(self, kind, q, k, v, sink, prec):
        """Causal self-attention over the S tokens of a prompt at
        positions 0..S-1: q [B,S,Hkv,G,Dk], k [B,S,Hkv,Dk],
        v [B,S,Hkv,Dv] -> [B,S,Hkv,G,Dv]. Full layers a prompt at a
        time, by chunks of queries over the keys up to the chunk's
        last query; window layers by chunks of `window` queries over
        their own and the previous chunk's keys: no [S, S] score
        matrix is ever whole."""
        import jax.numpy as jnp
        from jax import lax

        B, S, Hkv, G, Dk = q.shape
        scale = 1.0 / float(np.sqrt(Dk))
        if kind == FULL:
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
                    p = self._probs(s, mask, None).astype(vr.dtype)
                    out.append(jnp.einsum("hgqk,khd->qhgd", p, vr[:hi],
                                          precision=prec))
                return jnp.concatenate(out, 0)

            return lax.map(prompt, (q, k, v))
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
        p = self._probs(s, mask, sink[None, None, :, :, None, None]
                        ).astype(v.dtype)
        out = jnp.einsum("bchgqk,bckhd->bcqhgd", p, vc, precision=prec)
        return out.reshape(B, n * W, Hkv, G, -1)[:, :S]

    def _attend_slab(self, kind, q, k_all, v_all, pos, sink, prec):
        """One query a row against its cache: q [B,Hkv,G,Dk], k_all
        [B,Hkv,Dk,T], v_all [B,Hkv,T,Dv], row b at position pos[b]
        (already written). A context holds position j at j; a ring
        holds the last `window` positions at j mod window."""
        import jax.numpy as jnp

        T = v_all.shape[2]
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
        s = jnp.einsum("bhgd,bhdt->bhgt", q, k_all, precision=prec,
                       preferred_element_type=jnp.float32) * scale
        j = jnp.arange(T)[None, :]
        mask = j <= pos[:, None]
        if kind == WINDOW:
            mask = mask | (pos[:, None] >= T - 1)
            sink = sink[None, :, :, None]
        p = self._probs(s, mask[:, None, None, :], sink).astype(v_all.dtype)
        return jnp.einsum("bhgt,bhtd->bhgd", p, v_all, precision=prec)

    def _experts(self, ffn, x, prec):
        """The held experts' part of a routed layer for x [N, d], and
        the held experts' assignment counts [count]. Routes over all
        `n_experts`; drops nothing."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        first, E = self.held
        K = self.experts_per_token
        N = x.shape[0]
        with jax.named_scope("moe_router"):
            sig = jax.nn.sigmoid(jnp.matmul(
                x.astype(jnp.float32), ffn["W_r"].astype(jnp.float32),
                precision=lax.Precision.HIGHEST))
            _, idx = lax.top_k(sig + ffn["b"], K)              # [N,K]
            chosen = jnp.take_along_axis(sig, idx, -1)
            w = chosen / jnp.sum(chosen, -1, keepdims=True)
            local = idx - first
            here = (local >= 0) & (local < E)
            local = jnp.where(here, local, E)                 # E: elsewhere
            counts = jnp.zeros(E + 1, jnp.int32).at[
                local.reshape(-1)].add(1)[:E]
        with jax.named_scope("moe_experts"):
            if N <= self.dense_rows:
                # every held expert over every row, weighted by the
                # row's share for it (0 where it was not chosen)
                cw = jnp.sum(jax.nn.one_hot(local, E + 1, dtype=jnp.float32)
                             [..., :E] * w[..., None], 1)      # [N,E]
                g = jnp.einsum("nd,edf->enf", x, ffn["W_g"], precision=prec)
                u = jnp.einsum("nd,edf->enf", x, ffn["W_u"], precision=prec)
                a = jax.nn.silu(g) * u * cw.T[:, :, None].astype(x.dtype)
                y = jnp.einsum("enf,efd->nd", a, ffn["W_d"], precision=prec)
                return y, counts
            # assignments sorted by expert (those routed elsewhere
            # last), each group through its expert
            flat = local.reshape(-1)
            order = jnp.argsort(flat, stable=True)
            ws = jnp.where(here, w, 0.0).reshape(-1)
            at = jnp.zeros_like(order).at[order].set(
                jnp.arange(N * K, dtype=order.dtype)).reshape(N, K)
            rd = functools.partial(lax.ragged_dot, group_sizes=counts,
                                   precision=prec)

            def through(rows):
                """The first `rows` sorted assignments (static; they
                hold every local one) through their experts and back
                to their tokens."""
                o = order[:rows]
                xs = x[o // K]
                a = jax.nn.silu(rd(xs, ffn["W_g"])) * rd(xs, ffn["W_u"])
                y = rd(a, ffn["W_d"])                          # [rows,d]
                # rows past the held groups were multiplied by no
                # expert: whatever they hold is replaced, never weighted
                y = jnp.where((flat[o] < E)[:, None],
                              y * ws[o][:, None].astype(y.dtype), 0)
                y = jnp.where((at < rows)[..., None],
                              y[jnp.minimum(at, rows - 1)], 0)  # [N,K,d]
                return y.astype(jnp.float32).sum(1).astype(x.dtype)

            # a sixteenth of the assignments is local where the held
            # experts get their share: a quarter of the rows carries
            # that at a quarter of the gathers; N*K rows hold any
            # imbalance, so nothing is dropped
            few = N * K // 4
            y = lax.cond(counts.sum() <= few, lambda: through(few),
                         lambda: through(N * K))
            return y, counts

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
                q = self._rope(q.reshape(B, S, Hq, Dk), pos, kind)
                k = self._rope(k.reshape(B, S, Hkv, Dk), pos, kind)
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
                with jax.named_scope("dense_mlp"):
                    g = jnp.matmul(x, ffn["W_g"], precision=prec)
                    u = jnp.matmul(x, ffn["W_u"], precision=prec)
                    h = h + jnp.matmul(jax.nn.silu(g) * u, ffn["W_d"],
                                       precision=prec)
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
    def forward(self, x):
        """Logits [B, S, vocab] of ids [B, S]: the same stack with no
        cache. Eval only; nothing is recorded for a backward."""
        import jax
        import jax.numpy as jnp

        cache = self._program_cache()
        key_ = ("forward", self._trace_key())
        fn = cache.get(key_)
        if fn is None:
            def fwd(params, ids):
                B, S = ids.shape
                pos = jnp.broadcast_to(jnp.arange(S), (B, S))
                prec = tensor.get_matmul_precision()
                h, _ = self._stack(
                    params, ids, pos,
                    lambda li, kind, q, k, v, sink: self._attend_self(
                        kind, q, k, v, sink, prec))
                return self._head(params, h)

            fn = cache[key_] = jax.jit(fwd)
        return tensor.from_raw(fn(self._decode_params(), x.data), x.device)

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

    @staticmethod
    def _slab_sig(slab):
        return (tuple((tuple(c["k"].shape), tuple(c["v"].shape))
                      for c in slab), slab[0]["k"].dtype.name)

    @staticmethod
    def _slab_extra(slab):
        return [[list(c["k"].shape), list(c["v"].shape)] for c in slab]

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
            return self._attend_slab(kind, q[:, 0], k_all, v_all, pos,
                                     sink, prec)[:, None]

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
        from jax import lax

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

        def put_rows(cache, rows):
            """rows [Bp, ...] over the head of cache rows `slots`, a
            row at a time and in place; an out-of-bounds slot keeps
            what the (clamped) row held."""
            for b in range(Bp):
                start = (slots[b],) + (0,) * (cache.ndim - 1)
                old = lax.dynamic_slice(cache, start,
                                        (1,) + rows.shape[1:])
                row = jnp.where(slots[b] < cache.shape[0], rows[b:b + 1],
                                old)
                cache = lax.dynamic_update_slice(cache, row, start)
            return cache

        def attend(li, kind, q, k, v, sink):
            c = slab[li]
            if kind == FULL:
                kr, vr = k, v
            else:
                kr = jnp.take_along_axis(k, src[:, :, None, None], 1)
                vr = jnp.take_along_axis(v, src[:, :, None, None], 1)
            new.append({"k": put_rows(c["k"], kr.transpose(0, 2, 3, 1)),
                        "v": put_rows(c["v"], vr.transpose(0, 2, 1, 3))})
            return self._attend_self(kind, q, k, v, sink, prec)

        pos = jnp.broadcast_to(jnp.arange(Pb), (Bp, Pb))
        h, _ = self._stack(params, ids, pos, attend)
        last_h = jnp.take_along_axis(
            h, (n_real - 1)[:, None, None], axis=1)[:, 0]
        return self._head(params, last_h), new


def create_model(vocab_size=256, **kwargs):
    return HybridWindowMoELM(vocab_size, **kwargs)
