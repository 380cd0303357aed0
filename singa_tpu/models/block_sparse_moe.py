"""A mixture-of-experts LM whose attention reads, for each query and
each group of query heads that shares a key/value head, only the
`block`-position blocks of keys that a learned indexer picks
(MiniMax-M3's MSA), served as ONE chip's share of an expert-parallel
deployment. No bias anywhere; G key/value groups of Hg = H / G query
heads; J indexer heads a group of width Di; N = `block`.

    n(h, g)  = h / sqrt(mean(h^2) + eps) * (1 + g)        float32
    x = n(h, ln1)
    q = x W_q -> [H, D];  k = x W_k, v = x W_v -> [G, D]
    q, k = n(., q_norm / k_norm) per head over D, THEN rotary over the
           first `rotary_dim` dims (rotate-half); head h is in group h // Hg
    indexer:  qI[t,g,j] = x_t WqI[g,j] in R^Di,  wI[t,g,j] = x_t WwI[g,j],
              kI[s,g] = x_s WkI[g] in R^Di (no rotary)
    pooled key of block b:  Kb[b,g] = max_{s in b} kI[s,g]   elementwise
    score:    sig[t,g,b] = sum_j wI[t,g,j] * relu(qI[t,g,j] . Kb[b,g])
    c = t // N; read block 0, blocks c - local + 1 .. c, and the `top`
    highest-scoring of the complete blocks 1 .. c - local (ties: the
    lower index); every block up to c while there are no more than
    `top` candidates
    o[t,h] = sum_{s <= t, block(s) read} softmax_s(q.k / sqrt(D)) v[s,g]
    h += concat_heads(o) W_o
    x = n(h, ln2)
    act(g, u) = swigluoai: min(g,7) sigmoid(1.702 min(g,7)) (clip(u,+-7) + 1)
    dense:  h += act(x W_g, x W_u) W_d
    MoE:    `routed_experts`: sig = sigmoid(x W_r), top-k by sig + b,
            w_e = sig_e / sum_S sig; h += scale * sum_{held} w_e E_e(x)
            + E_shared(x)                     (the shared expert: no gate)
    logits = n(h_last, ln_f) W_head

`held = (first, count)` names the routed experts this chip holds; the
shared expert is computed whole on every chip. One functional stack
(`_layer`) serves `forward()` in eval mode, the one-prompt prefill and
the fused decode step; there is no backward, so `train_one_batch`
raises. The residual stream is float32, and so is the indexer: its
projection takes x before its rounding, its pooled keys are kept in
float32 and its scores are products at HIGHEST, because its choice is
a step function of scores that lie close together (PERF.md section 6,
PR 39).

The slab holds, a layer, keys [slots, G, D, T] (a position a column)
and values [slots, G, T, D] (a position a row), climbing the sequence
ladder, and a third kind, `blockkey`: the pooled indexer keys [slots,
G, Di, T / N] in float32, one column a block. A decode step writes its key and
value at `pos` (`cache_write`) and folds its kI into column pos // N
by a running max (a block entered at pos % N == 0 starts from its
first key: what the column held before is another session's or
float32's lowest value, which stands for minus infinity); the indexer
scores the row's complete blocks from that list, `lax.top_k` picks,
and the attention moves only the picked blocks of K and V
(`selected_blocks_attend`, where the block is a whole number of lane
tiles; a gather of the same blocks elsewhere). A prompt runs
`prefill_block` positions at a time inside one program, its keys,
values and pooled keys the loop's carry, each query's selection a mask
over key tiles of a running softmax: no score array over every key of
a block of queries exists. The step counts what it read
(`msa_positions_read`: positions of the blocks moved), what the rows
hold (`msa_positions_held`: 0 .. pos) and the blocks picked, each
summed over rows, layers and groups.
"""
from __future__ import annotations

import functools

import numpy as np

from .. import tensor
from .drawn_lm import RoutedDrawnLM, put_rows, rope
from .routed_experts import swigluoai


class BlockSparseMoELM(RoutedDrawnLM):
    """Causal LM over int token ids [B, S] -> logits [B, S, vocab]."""

    step_counter_names = RoutedDrawnLM.step_counter_names + (
        "msa_positions_read", "msa_positions_held", "msa_blocks_selected")
    _slab_words = "contexts and pooled block keys"
    _training_lacks = ("with no backward for the indexer's selection, "
                       "normed rotary heads or the routed experts, and "
                       "no optimizer state for a share of the experts")

    def __init__(self, vocab_size: int, d_model: int = 6144,
                 num_heads: int = 64, kv_heads: int = 4, head_dim: int = 128,
                 rotary_dim: int = 64, rope_theta: float = 5e6,
                 index_heads: int = 4, index_dim: int = 128,
                 block: int = 128, top_blocks: int = 16,
                 local_blocks: int = 2, moe_layers=(0, 1, 1, 1, 1),
                 d_ff: int = 12288, d_ff_expert: int = 3072,
                 d_ff_shared: int = 3072, n_experts: int = 128,
                 experts_per_token: int = 4, held=(0, 8),
                 routed_scale: float = 2.0, swiglu_alpha: float = 1.702,
                 swiglu_limit: float = 7.0, norm_eps: float = 1e-6,
                 max_len: int = 32768, prefill_block: int = 4096,
                 prefill_tile: int = 512, param_dtype: str = "float32",
                 init_std: float = 0.02):
        super().__init__()
        if num_heads % kv_heads or rotary_dim % 2 or rotary_dim > head_dim:
            raise ValueError(f"{num_heads} heads over {kv_heads} groups, "
                             f"rotary_dim {rotary_dim} of {head_dim}")
        if (block < 1 or prefill_block % block or prefill_tile % block
                or prefill_block % prefill_tile or local_blocks < 1):
            raise ValueError(f"blocks of {block} in prefill blocks of "
                             f"{prefill_block} and tiles of {prefill_tile}, "
                             f"{local_blocks} local")
        self._init_drawn(vocab_size, max_len, norm_eps, param_dtype, init_std,
                         n_experts, experts_per_token, held)
        self.d_model, self.num_heads = int(d_model), int(num_heads)
        self.kv_heads, self.head_dim = int(kv_heads), int(head_dim)
        self.rotary_dim, self.rope_theta = int(rotary_dim), float(rope_theta)
        self.index_heads, self.index_dim = int(index_heads), int(index_dim)
        self.block, self.top_blocks = int(block), int(top_blocks)
        self.local_blocks = int(local_blocks)
        self.moe_layers = tuple(int(v) for v in moe_layers)
        self.d_ff, self.d_ff_expert = int(d_ff), int(d_ff_expert)
        self.d_ff_shared = int(d_ff_shared)
        self.routed_scale = float(routed_scale)
        self.expert_act = functools.partial(
            swigluoai, alpha=float(swiglu_alpha), limit=float(swiglu_limit))
        self.prefill_block, self.prefill_tile = (int(prefill_block),
                                                 int(prefill_tile))

    @property
    def num_layers(self):
        return len(self.moe_layers)

    @property
    def index_width(self):
        """Columns of the indexer's one projection: qI, then kI, then
        wI, each group-major."""
        G, J, Di = self.kv_heads, self.index_heads, self.index_dim
        return G * J * Di + G * Di + G * J

    def _param_table(self):
        """Every parameter: (dotted name under the model, shape, dtype,
        std of its normal draw or None, constant value or None)."""
        d, H, G, D = self.d_model, self.num_heads, self.kv_heads, self.head_dim
        pd, f32, std = self.param_dtype, np.dtype("float32"), self.init_std
        E, f, fs = self.held[1], self.d_ff_expert, self.d_ff_shared
        # the norms' gains are 1 + g with g drawn, not zero, and the
        # router's bias too: a dropped `+ 1` or bias shows
        out = [("embed.W", (self.vocab_size, d), pd, std, None)]
        for li, routed in enumerate(self.moe_layers):
            pre = f"blocks.l{li}."
            out += [
                (pre + "ln1.g", (d,), f32, 0.1, None),
                (pre + "attn.W_qkv", (d, (H + 2 * G) * D), pd, std, None),
                (pre + "attn.q_norm", (D,), f32, 0.1, None),
                (pre + "attn.k_norm", (D,), f32, 0.1, None),
                (pre + "attn.W_o", (H * D, d), pd, std, None),
                (pre + "index.W", (d, self.index_width), pd, std, None),
                (pre + "ln2.g", (d,), f32, 0.1, None)]
            if routed:
                out += [
                    (pre + "moe.W_r", (d, self.n_experts), f32, std, None),
                    (pre + "moe.b", (self.n_experts,), f32, 0.1, None),
                    (pre + "moe.W_g", (E, d, f), pd, std, None),
                    (pre + "moe.W_u", (E, d, f), pd, std, None),
                    (pre + "moe.W_d", (E, f, d), pd, std, None),
                    (pre + "shared.W_g", (d, fs), pd, std, None),
                    (pre + "shared.W_u", (d, fs), pd, std, None),
                    (pre + "shared.W_d", (fs, d), pd, std, None)]
            else:
                out += [(pre + "mlp.W_g", (d, self.d_ff), pd, std, None),
                        (pre + "mlp.W_u", (d, self.d_ff), pd, std, None),
                        (pre + "mlp.W_d", (self.d_ff, d), pd, std, None)]
        return out + [("ln_f.g", (d,), f32, 0.1, None),
                      ("head.W", (d, self.vocab_size), pd, std, None)]

    def _tree(self, leaf):
        """The tree every program receives, from `leaf(dotted name)`."""
        blocks = []
        for li, routed in enumerate(self.moe_layers):
            pre = f"blocks.l{li}."
            blk = {"ln1": leaf(pre + "ln1.g"), "ln2": leaf(pre + "ln2.g"),
                   "attn": {n: leaf(pre + "attn." + n)
                            for n in ("W_qkv", "q_norm", "k_norm", "W_o")},
                   "index": leaf(pre + "index.W")}
            if routed:
                blk["ffn"] = {n: leaf(pre + "moe." + n)
                              for n in ("W_r", "b", "W_g", "W_u", "W_d")}
                blk["shared"] = {n: leaf(pre + "shared." + n)
                                 for n in ("W_g", "W_u", "W_d")}
            else:
                blk["mlp"] = {n: leaf(pre + "mlp." + n)
                              for n in ("W_g", "W_u", "W_d")}
            blocks.append(blk)
        return {"embed": leaf("embed.W"), "blocks": blocks,
                "ln_f": leaf("ln_f.g"), "head": leaf("head.W")}

    # -- what is not implemented, by mechanism -----------------------------
    def _shard_decode_params(self, params, mesh):
        raise NotImplementedError(
            "BlockSparseMoELM: the tensor-parallel shard path is not "
            "implemented: 4 key/value groups do not divide over a mesh, "
            "and experts across chips need their exchange")

    # -- the mathematics ---------------------------------------------------
    def _norm(self, h, g, dtype):
        """Gemma's RMSNorm (gain 1 + g) in float32, in `dtype`."""
        import jax.numpy as jnp

        return self._rms(h.astype(jnp.float32), 1.0 + g).astype(dtype)

    def _mlp(self, ffn, x, prec):
        """act(x W_g, x W_u) W_d: the dense layer and the shared expert."""
        import jax.numpy as jnp

        return jnp.matmul(self.expert_act(
            jnp.matmul(x, ffn["W_g"], precision=prec),
            jnp.matmul(x, ffn["W_u"], precision=prec)), ffn["W_d"],
            precision=prec)

    def _layer(self, blk, h, pos, attend):
        """One layer over h [B, S, d] (float32) at positions pos [B, S].
        `attend(q, k, v, qi, ki, wi)` takes the rotated q [B,S,G,Hg,D],
        k [B,S,G,D] and v, the indexer's qI [B,S,G,J,Di], kI [B,S,G,Di]
        and wI [B,S,G,J], keeps what its cache keeps and returns o
        [B,S,G,Hg,D]. Returns (h, the routed layer's counters [3])."""
        import jax
        import jax.numpy as jnp

        prec = tensor.get_matmul_precision()
        B, S, _ = h.shape
        H, G, D = self.num_heads, self.kv_heads, self.head_dim
        J, Di = self.index_heads, self.index_dim
        at = blk["attn"]
        dt = at["W_qkv"].dtype
        xf = self._norm(h, blk["ln1"], jnp.float32)
        x = xf.astype(dt)
        q, k, v = jnp.split(jnp.matmul(x, at["W_qkv"], precision=prec),
                            [H * D, (H + G) * D], -1)
        q = rope(self._norm(q.reshape(B, S, H, D), at["q_norm"], dt), pos,
                 self.rope_theta, self.rotary_dim)
        k = rope(self._norm(k.reshape(B, S, G, D), at["k_norm"], dt), pos,
                 self.rope_theta, self.rotary_dim)
        with jax.named_scope("msa_indexer"):
            qi, ki, wi = jnp.split(self._index(xf, blk["index"]),
                                   [G * J * Di, G * J * Di + G * Di], -1)
        o = attend(q.reshape(B, S, G, H // G, D), k, v.reshape(B, S, G, D),
                   qi.reshape(B, S, G, J, Di), ki.reshape(B, S, G, Di),
                   wi.reshape(B, S, G, J))
        h = h + jnp.matmul(o.reshape(B, S, H * D).astype(dt), at["W_o"],
                           precision=prec)
        x = self._norm(h, blk["ln2"], dt)
        if "ffn" not in blk:
            return h + self._mlp(blk["mlp"], x, prec), jnp.zeros(3, jnp.int32)
        y, counts = self._experts(blk["ffn"], x.reshape(B * S, -1), prec)
        with jax.named_scope("moe_shared"):
            y = y.reshape(B, S, -1) + self._mlp(blk["shared"], x, prec)
        return h + y, jnp.stack([counts.sum(), (counts > 0).sum(),
                                 counts.max()])

    def _head(self, params, h):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("head"):
            x = self._norm(h, params["ln_f"], params["head"].dtype)
            return jnp.matmul(x, params["head"],
                              precision=tensor.get_matmul_precision(),
                              preferred_element_type=jnp.float32)

    # -- the indexer -------------------------------------------------------
    @staticmethod
    def _index(xf, w):
        """The indexer's projection of the normed input xf [..., d]
        (float32) in float32: xf as the sum of two parts in the stored
        matrix's dtype, each through the matrix, summed in float32 (the
        rounding of xf alone moves near-tied block scores past each
        other: PERF.md section 6, PR 39)."""
        import jax.numpy as jnp

        hi = xf.astype(w.dtype)
        lo = (xf - hi.astype(jnp.float32)).astype(w.dtype)
        # the layer's precision: exact for bfloat16 parts at any, and
        # float32 parts need "highest" on the chip (its default rounds
        # them to bfloat16 in the product)
        return sum(jnp.matmul(t, w, preferred_element_type=jnp.float32,
                              precision=tensor.get_matmul_precision())
                   for t in (hi, lo))

    def _scores(self, qi, wi, kp):
        """sig [..., G, nb] = sum_j wI_j relu(qI_j . Kb) in float32, for
        qI [..., G, J, Di], wI [..., G, J] and the pooled keys kp [B, G,
        Di, nb] (`...` leads with B). A column of the dtype's lowest
        value scores garbage: the caller masks every block that is not
        complete."""
        import jax
        import jax.numpy as jnp

        s = jnp.einsum("b...gjd,bgdn->b...gjn", qi, kp,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.einsum("b...gjn,b...gj->b...gn", jax.nn.relu(s),
                          wi.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)

    def _selection(self, sig, c):
        """[..., nb] bool: the blocks a query in block c [...] reads,
        from its scores sig [..., nb]: block 0, its local blocks, and
        the `top_blocks` best-scoring candidates (lower index first on
        ties; all of them while there are no more)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        nb = sig.shape[-1]
        b = jnp.arange(nb)
        # the complete blocks before the local ones, block 0 aside
        cand = (b >= 1) & (b <= c[..., None] - self.local_blocks)
        top = min(self.top_blocks, nb)
        _, idx = lax.top_k(jnp.where(cand, sig, -jnp.inf), top)
        ranked = jnp.arange(top) < jnp.sum(cand, -1, keepdims=True)
        picked = jnp.any(jax.nn.one_hot(idx, nb, dtype=jnp.bool_)
                         & ranked[..., None], -2)
        fixed = (b == 0) | ((b <= c[..., None])
                            & (b > c[..., None] - self.local_blocks))
        return picked | fixed

    def selected_ids(self, mask):
        """(ids [..., S] int32 ascending, count [...]) of the blocks a
        selection mask [..., nb] reads, S = 1 + local + top (at most
        nb); entries past the count are 0."""
        import jax.numpy as jnp
        from jax import lax

        nb = mask.shape[-1]
        S = min(1 + self.local_blocks + self.top_blocks, nb)
        neg, _ = lax.top_k(jnp.where(mask, -jnp.arange(nb, dtype=jnp.float32),
                                     -jnp.inf), S)
        n = jnp.sum(mask, -1).astype(jnp.int32)
        ids = jnp.where(jnp.arange(S) < n[..., None], -neg, 0)
        return ids.astype(jnp.int32), n

    def _pool(self, ki, real):
        """Pooled keys [B, G, Di, n] of kI [B, n*N, G, Di]: the max over
        each block's positions where `real` [B, n*N]; the dtype's lowest
        value where a block has none."""
        import jax.numpy as jnp

        B, L, G, Di = ki.shape
        low = jnp.finfo(ki.dtype).min
        k = jnp.where(real[..., None, None], ki, low)
        return jnp.max(k.reshape(B, L // self.block, self.block, G, Di),
                       2).transpose(0, 2, 3, 1)

    # -- a prompt, a block of positions at a time --------------------------
    def _attend_prompt(self, q, qi, wi, K, V, kp, start, picks=False):
        """The queries of one prompt block, q [B, L, G, Hg, D] at
        positions start .. start + L - 1, over the prompt's keys K [B, G,
        D, S] and values V [B, G, S, D] (this block's written), each
        reading the blocks its selection from kp [B, G, Di, S / N] picks:
        a loop over tiles of `prefill_tile` queries, each a loop over the
        key tiles up to its own whose running softmax masks what the
        selection leaves out. -> [B, L, G, Hg, D], and with `picks` the
        ids of the blocks each query and group read [B, L, G, width]."""
        import jax.numpy as jnp
        from jax import lax

        prec = tensor.get_matmul_precision()
        B, L, G, Hg, D = q.shape
        N = self.block
        C = min(self.prefill_tile, L)
        nbk = C // N
        scale = 1.0 / float(np.sqrt(D))

        def queries(i, carry):
            out, picked = carry
            lo = i * C
            qc = lax.dynamic_slice_in_dim(q, lo, C, 1)
            qpos = start + lo + jnp.arange(C)
            sel = self._selection(self._scores(
                lax.dynamic_slice_in_dim(qi, lo, C, 1),
                lax.dynamic_slice_in_dim(wi, lo, C, 1), kp),
                jnp.broadcast_to(qpos // N, (B, G, C)).transpose(0, 2, 1))

            def keys(t, carry):
                m, l, acc = carry
                kk = lax.dynamic_slice_in_dim(K, t * C, C, 3)
                vv = lax.dynamic_slice_in_dim(V, t * C, C, 2)
                s = jnp.einsum("bqghd,bgdk->bgqhk", qc, kk, precision=prec,
                               preferred_element_type=jnp.float32) * scale
                blocks = lax.dynamic_slice_in_dim(sel, t * nbk, nbk, 3)
                ok = (jnp.repeat(blocks, N, -1).transpose(0, 2, 1, 3)
                      & (t * C + jnp.arange(C)[None, :] <= qpos[:, None]))
                s = jnp.where(ok[:, :, :, None, :], s, -1e30)
                m2 = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
                p = jnp.exp(s - m2)
                a = jnp.exp(m - m2)
                return (m2, a * l + jnp.sum(p, -1, keepdims=True),
                        a * acc + jnp.einsum(
                            "bgqhk,bgkd->bgqhd", p.astype(vv.dtype), vv,
                            precision=prec,
                            preferred_element_type=jnp.float32))

            m, l, acc = lax.fori_loop(
                0, (start + lo) // C + 1, keys,
                (jnp.full((B, G, C, Hg, 1), -1e30, jnp.float32),
                 jnp.zeros((B, G, C, Hg, 1), jnp.float32),
                 jnp.zeros((B, G, C, Hg, D), jnp.float32)))
            o = (acc / l).astype(q.dtype).transpose(0, 2, 1, 3, 4)
            if picks:
                picked = lax.dynamic_update_slice_in_dim(
                    picked, self.selected_ids(sel)[0], lo, 1)
            return lax.dynamic_update_slice_in_dim(out, o, lo, 1), picked

        width = min(1 + self.local_blocks + self.top_blocks, kp.shape[3])
        out, picked = lax.fori_loop(
            0, L // C, queries,
            (jnp.zeros_like(q), jnp.zeros((B, L, G, width), jnp.int32)
             if picks else None))
        return (out, picked) if picks else out

    def _prompts(self, params, ids, n_real, keep_all=False, picks=False):
        """The stack over prompts ids [B, S] at positions 0 .. S-1, a
        block of `prefill_block` positions at a time (a loop over the
        blocks that hold a real position of some row, whose carry is
        every layer's keys, values and pooled keys so far). Returns
        (hidden: the last real position's [B, d], or with `keep_all`
        every position's [B, S, d]; for each layer what a slab row takes
        of a prompt whose first n_real [B] positions are real: {"k": [B,
        G, D, S'], "v": [B, G, S', D], "kp": [B, G, Di, S' / N]}, S' the
        bucket padded to whole blocks, the pooled keys over real
        positions alone; and with `picks` each layer's selected block ids
        [B, S, G, width])."""
        import jax.numpy as jnp
        from jax import lax

        B, S = ids.shape
        N, P = self.block, self.prefill_block
        G, D, Di = self.kv_heads, self.head_dim, self.index_dim
        dt = params["embed"].dtype
        Sp = -(-S // N) * N
        Lb = P if Sp > P else Sp
        Sp = -(-Sp // Lb) * Lb
        nblk = Sp // Lb
        ids = jnp.pad(ids, ((0, 0), (0, Sp - S)))
        last = (n_real - 1) // Lb
        low = jnp.finfo(jnp.float32).min
        rows = [(jnp.zeros((B, G, D, Sp), dt), jnp.zeros((B, G, Sp, D), dt),
                 jnp.full((B, G, Di, Sp // N), low, jnp.float32))
                for _ in range(self.num_layers)]
        keep = (jnp.zeros((nblk, B, Lb, self.d_model), jnp.float32)
                if keep_all else jnp.zeros((B, self.d_model), jnp.float32))
        width = min(1 + self.local_blocks + self.top_blocks, Sp // N)
        chosen = ([jnp.zeros((B, Sp, G, width), jnp.int32)] * self.num_layers
                  if picks else None)

        def block(w, carry):
            rows, keep, chosen = carry
            start = w * Lb
            pos = start + jnp.broadcast_to(jnp.arange(Lb), (B, Lb))
            real = pos < n_real[:, None]
            h = params["embed"][lax.dynamic_slice_in_dim(ids, start, Lb, 1)
                                ].astype(jnp.float32)
            new, now = [], []
            for blk, (K, V, kp) in zip(params["blocks"], rows):
                def attend(q, k, v, qi, ki, wi, K=K, V=V, kp=kp):
                    K = lax.dynamic_update_slice_in_dim(
                        K, k.transpose(0, 2, 3, 1), start, 3)
                    V = lax.dynamic_update_slice_in_dim(
                        V, v.transpose(0, 2, 1, 3), start, 2)
                    kp = lax.dynamic_update_slice_in_dim(
                        kp, self._pool(ki, real), start // N, 3)
                    new.append((K, V, kp))
                    o = self._attend_prompt(q, qi, wi, K, V, kp, start, picks)
                    if not picks:
                        return o
                    now.append(o[1])
                    return o[0]

                h, _ = self._layer(blk, h, pos, attend)
            if keep_all:
                keep = lax.dynamic_update_index_in_dim(keep, h, w, 0)
            else:
                at = jnp.clip(n_real - 1 - start, 0, Lb - 1)
                mine = (w == last)[:, None]
                keep = jnp.where(mine, jnp.take_along_axis(
                    h, at[:, None, None], 1)[:, 0], keep)
            if picks:
                chosen = [lax.dynamic_update_slice_in_dim(c, p, start, 1)
                          for c, p in zip(chosen, now)]
            return new, keep, chosen

        rows, keep, chosen = lax.fori_loop(0, jnp.max(last) + 1, block,
                                           (rows, keep, chosen))
        if keep_all:
            keep = keep.swapaxes(0, 1).reshape(B, Sp, -1)[:, :S]
        rows = [{"k": K, "v": V, "kp": kp} for K, V, kp in rows]
        if picks:
            return keep, rows, [c[:, :S] for c in chosen]
        return keep, rows

    # -- eval forward --------------------------------------------------------
    def _eval_logits(self, params, ids):
        import jax.numpy as jnp

        h, _ = self._prompts(params, ids, jnp.full(ids.shape[:1],
                                                   ids.shape[1]), True)
        return self._head(params, h)

    def picks(self, x):
        """Each layer's selected block ids [B, S, G, width] (ascending,
        0 past a count) of the queries along ids x [B, S], as the
        prefill selects them: what a reference's selection is held to.
        Eval only."""
        import jax
        import jax.numpy as jnp

        cache = self._program_cache()
        key_ = ("picks", self._trace_key())
        fn = cache.get(key_)
        if fn is None:
            fn = cache[key_] = jax.jit(lambda p, ids: self._prompts(
                p, ids, jnp.full(ids.shape[:1], ids.shape[1]),
                picks=True)[2])
        params = self._decode_params()
        return fn(params, jax.device_put(
            x.data, jax.tree_util.tree_leaves(params)[0].sharding))

    # -- the slab: contexts beside pooled block keys -------------------------
    def new_slab(self, params, slots, seq, device):
        """Per layer {"k": [slots, G, D, T], "v": [slots, G, T, D],
        "kp": [slots, G, Di, T / N]} with T = `seq`; the pooled keys
        float32 (the indexer's dtype), starting at its lowest value."""
        import jax.numpy as jnp

        if seq % self.block:
            raise ValueError(f"a rung of {seq} positions is no whole number "
                             f"of blocks of {self.block}")
        dt = params["embed"].dtype
        G, D, Di = self.kv_heads, self.head_dim, self.index_dim
        nb = seq // self.block
        return [{"k": jnp.zeros((slots, G, D, seq), dt, device=device),
                 "v": jnp.zeros((slots, G, seq, D), dt, device=device),
                 "kp": jnp.full((slots, G, Di, nb), jnp.finfo(jnp.float32).min,
                                jnp.float32, device=device)}
                for _ in range(self.num_layers)]

    def grow_slab(self, slab, new_seq):
        """Every kind grows with the rung."""
        import jax.numpy as jnp

        def grown(c):
            more = new_seq - c["v"].shape[2]
            pad = ((0, 0),) * 3 + ((0, more // self.block),)
            return {"k": jnp.pad(c["k"], ((0, 0),) * 3 + ((0, more),)),
                    "v": jnp.pad(c["v"], ((0, 0), (0, 0), (0, more),
                                          (0, 0))),
                    "kp": jnp.pad(c["kp"], pad, constant_values=jnp.finfo(
                        c["kp"].dtype).min)}

        return [grown(c) for c in slab]

    def slab_dims(self, slab):
        v = slab[0]["v"]
        return int(v.shape[0]), int(v.shape[2])

    @staticmethod
    def slab_bytes(slab):
        out = {"context": 0, "blockkey": 0}
        for c in slab:
            for n, a in c.items():
                out["blockkey" if n == "kp" else "context"] += (
                    a.size * a.dtype.itemsize)
        return out

    # -- the programs' step functions --------------------------------------
    def _slot_step(self, params, slab, tok, pos):
        """One fused decode step over every slot at per-row positions.
        Row b writes its key and value at pos[b] and folds its kI into
        pooled key pos[b] // N (in place); each of its groups scores its
        complete blocks, picks, and attends the picked blocks alone.
        Returns (logits [B, V], new slab, counters [6]: the routed
        layers' three, then positions read, positions held and blocks
        picked)."""
        import jax
        import jax.numpy as jnp

        from ..ops.pallas_kernels import (cache_write, selected_blocks_attend,
                                          selected_blocks_lower)

        B = tok.shape[0]
        N, G = self.block, self.kv_heads
        T = slab[0]["v"].shape[2]
        nb = slab[0]["kp"].shape[3]
        c = pos // N
        kernel = selected_blocks_lower(N, T)
        new, picked = [], []

        def attend(q, k, v, qi, ki, wi, cache):
            with jax.named_scope("msa_indexer"):
                kp = cache["kp"]
                col = (jnp.arange(nb)[None, :] == c[:, None])[:, None, None]
                fresh = (pos % N == 0)[:, None, None, None]
                kin = ki[:, 0, :, :, None]
                kp = jnp.where(col, jnp.where(fresh, kin,
                                              jnp.maximum(kp, kin)), kp)
                sel = self._selection(
                    self._scores(qi[:, 0], wi[:, 0], kp),
                    jnp.broadcast_to(c[:, None], (B, G)))
                ids, n = self.selected_ids(sel)
            with jax.named_scope("attn_sparse"):
                K = cache_write(cache["k"], k[:, 0], pos, axis=3)
                V = cache_write(cache["v"], v[:, 0], pos, axis=2)
                if kernel:
                    o = selected_blocks_attend(q[:, 0], K, V, ids, n, pos, N)
                else:
                    o = attend_selected(q[:, 0], K, V, ids, n, pos, N)
            new.append({"k": K, "v": V, "kp": kp})
            picked.append(n)
            return o[:, None]

        h = params["embed"][tok][:, None].astype(jnp.float32)
        counters = jnp.zeros(3, jnp.int32)
        for blk, cache in zip(params["blocks"], slab):
            h, counts = self._layer(
                blk, h, pos[:, None],
                functools.partial(attend, cache=cache))
            counters = counters + counts
        n = jnp.sum(jnp.stack(picked))
        held = self.num_layers * G * jnp.sum(pos + 1)
        counters = jnp.concatenate([counters, jnp.stack(
            [N * n, held, n]).astype(jnp.int32)])
        return self._head(params, h[:, 0]), new, counters

    def _prefill_rows(self, params, slab, ids, n_real, slots):
        """A cohort of bucket-padded prompts [Bp, Pb] through the
        stack, their keys, values and pooled keys written into slab
        rows `slots` (a row whose slot is out of bounds writes nothing):
        positions 0 .. Pb-1 (the pad tail is masked by causality and
        overwritten before a query sees it), the pooled keys of the
        blocks that hold real positions over those alone and the
        lowest value for the rest."""
        h, rows = self._prompts(params, ids, n_real)
        new = [{n: put_rows(a, row[n], slots) for n, a in c.items()}
               for c, row in zip(slab, rows)]
        return self._head(params, h), new


def attend_selected(q, k, v, ids, n_sel, pos, block):
    """`selected_blocks_attend`'s mathematics through XLA: the selected
    blocks gathered out of k [B, G, D, T] and v [B, G, T, D] (the
    `block`-position blocks ids [B, G, S], the first n_sel [B, G] of
    them), one softmax over their positions up to pos [B] for q [B, G,
    Hg, D] -> [B, G, Hg, D] float32. The path where the kernel does not
    lower, and its oracle."""
    import jax.numpy as jnp

    from .drawn_lm import softmax_probs

    B, G, D, T = k.shape
    S, N = ids.shape[2], int(block)
    kb = jnp.take_along_axis(k.reshape(B, G, D, T // N, N),
                             ids[:, :, None, :, None], 3)   # [B,G,D,S,N]
    vb = jnp.take_along_axis(v.reshape(B, G, T // N, N, D),
                             ids[:, :, :, None, None], 2)   # [B,G,S,N,D]
    s = jnp.einsum("bghd,bgdsn->bghsn", q, kb,
                   preferred_element_type=jnp.float32) / float(np.sqrt(D))
    at = ids[..., None] * N + jnp.arange(N)                 # [B,G,S,N]
    ok = ((jnp.arange(S)[None, None, :, None] < n_sel[..., None, None])
          & (at <= pos[:, None, None, None]))
    p = softmax_probs(s.reshape(B, G, -1, S * N),
                      ok.reshape(B, G, 1, S * N), None)
    return jnp.einsum("bghk,bgkd->bghd", p.astype(v.dtype),
                      vb.reshape(B, G, S * N, D),
                      preferred_element_type=jnp.float32)


def create_model(vocab_size=256, **kwargs):
    return BlockSparseMoELM(vocab_size, **kwargs)
