"""A dense byte-level LM whose attention keeps exact keys and values
only inside the query's own block of `window` positions and sees every
earlier block as one learned-pooled key and value a chunk of `chunk`
positions, in ONE softmax over both (`model_type` `evabyte`,
`attention_class` `eva`). No bias anywhere; W = `window`, C = `chunk`.

    n   = RMSNorm(h) = h / sqrt(mean(h^2) + eps) * (1 + g)
    h  += Attn(RMSNorm(h));  h += (silu(n W_g) * n W_u) W_d    h in float32
    q_i, k_i, v_i = split3(n_i W_qkv) -> [H, D];  q, k rotated
                    (rotate-half, all D) at position i
    chunk c = positions cC .. cC+C-1, complete once position cC+C-1 exists:
        a_j = softmax_{j in c}(k_j . phi)         phi [H, D], a parameter
        sk_c = sum_j a_j k_j + mu                 mu  [H, D], a parameter
        sv_c = sum_j a_j v_j                      (k_j already rotated)
    query i, w = i // W:
        local   { j : j // W = w, j <= i }        exact, its own block only
        remote  { c : cC // W < w }               W/C summaries a block
        o_i = one softmax over q_i.k_j / sqrt(D) and q_i.sk_c / sqrt(D),
              in float32, weighting v_j and sv_c
    Attn = concat_heads(o) W_o
    logits = RMSNorm(h_last) W_head[:, :V]        float32; W_head [d, P*V]
             holds `pred_heads` P heads' columns, head 0 is the next byte

A chunk of the query's own block is never seen as a summary (its
positions are seen exactly), so at most W + (max_len/W - 1) * W/C
entries are ever attended.

One functional stack (`_stack`) serves `forward()` in eval mode, the
cohort prefill and the fused decode step; there is no backward, so
`train_one_batch` raises. The cache is of two kinds in one slab, every
array positions last. A `window` buffer, keys and values [slots, H, D,
W], holds position p at p mod W and is NOT a ring: entry j is valid
iff j <= pos mod W, and nothing older survives a block boundary. The
`summary` list, [slots, H, D, rung / C], climbs the sequence ladder at
a C-th of the rung; entry c is written once, by the step (or the
prefill) that completes chunk c, and is seen from the next block
boundary on. A decode step writes its key and value into the buffer,
attends, and then, for the rows whose chunk closes at this position,
pools the buffer's last C entries and writes their summary: a second,
conditional write a row (the rows whose chunk stays open write back
what the entry held), in place in the donated slab. What the step's
attention READS of row b: where buffer and list are whole numbers of
128-entry blocks (the buffer two or more), the buffer's blocks 0 ..
(pos[b] mod W) // 128 and the list's blocks 0 .. ceil(seen / 128) - 1
and nothing else of either (`window_summary_attend`, a kernel that
takes the four arrays where they lie; the step counts the entries as
`attn_entries_read`); at any other widths (the toys') every buffer and
every list whole, through two `einsum`s. The shapes alone decide. A prefill writes
the summaries of the chunks complete among a row's REAL positions
(zero for the rest of its bucket) and the buffer from the row's last
real block, whatever the bucket padded.
"""
from __future__ import annotations

import numpy as np

from .. import tensor
from .drawn_lm import (DrawnDecodeLM, dense_mlp, put_rows, rope,
                       softmax_probs)


class ChunkedAttnLM(DrawnDecodeLM):
    """Causal LM over int token ids [B, S] -> logits [B, S, vocab]."""

    # a run-ahead block as a loop: the steps of a dense model are alike
    # and nothing of it is re-laid around one (compiled for a described
    # v5e, `tests/test_tpu_compile_widths.py`)
    scan_unroll = 1
    step_counter_names = ("attn_entries_needed", "attn_entries_held",
                          "chunk_summaries_written", "attn_entries_read")
    _slab_words = "window buffers and chunk summaries"
    _training_lacks = ("with no backward for attention over chunk "
                       "summaries or rotary positions, and no optimizer "
                       "state")
    _one_chip_holds = "whole layers"

    def __init__(self, vocab_size: int, d_model: int = 4096,
                 num_heads: int = 32, head_dim: int = 128,
                 window: int = 2048, chunk: int = 16,
                 rope_theta: float = 1e5, num_layers: int = 6,
                 d_ff: int = 11008, pred_heads: int = 8,
                 norm_eps: float = 1e-5, max_len: int = 16384,
                 param_dtype: str = "float32", init_std: float = 0.01275):
        super().__init__()
        if head_dim % 2 or chunk < 1 or window % chunk:
            raise ValueError(f"head_dim {head_dim} (even), window {window} "
                             f"(a whole number of chunks of {chunk})")
        self._init_drawn(vocab_size, max_len, norm_eps, param_dtype,
                         init_std)
        self.d_model, self.num_heads = int(d_model), int(num_heads)
        self.head_dim, self.window = int(head_dim), int(window)
        self.chunk, self.rope_theta = int(chunk), float(rope_theta)
        self.num_layers, self.d_ff = int(num_layers), int(d_ff)
        self.pred_heads = int(pred_heads)

    def _param_table(self):
        """Every parameter: (dotted name under the model, shape, dtype,
        std of its normal draw or None, constant value or None)."""
        d, H, D = self.d_model, self.num_heads, self.head_dim
        pd, f32, std = self.param_dtype, np.dtype("float32"), self.init_std
        out = [("embed.W", (self.vocab_size, d), pd, std, None)]
        for li in range(self.num_layers):
            pre = f"blocks.l{li}."
            out += [
                # gains are 1 + g (`norm_add_unit_offset`); g drawn 0
                (pre + "ln1.g", (d,), f32, None, 0.0),
                (pre + "attn.W_qkv", (d, 3 * H * D), pd, std, None),
                # N(0, 1) * D^-0.5, clipped to one such std by
                # `_draw_params`: pooling is not uniform and the offset
                # not zero, so a fault in either shows
                (pre + "attn.phi", (H, D), f32, D ** -0.5, None),
                (pre + "attn.mu", (H, D), f32, D ** -0.5, None),
                (pre + "attn.W_o", (H * D, d), pd, std, None),
                (pre + "ln2.g", (d,), f32, None, 0.0),
                (pre + "mlp.W_g", (d, self.d_ff), pd, std, None),
                (pre + "mlp.W_u", (d, self.d_ff), pd, std, None),
                (pre + "mlp.W_d", (self.d_ff, d), pd, std, None)]
        return out + [
            ("ln_f.g", (d,), f32, None, 0.0),
            ("head.W", (d, self.pred_heads * self.vocab_size), pd, std, None)]

    def _draw_params(self, dev):
        import jax.numpy as jnp

        super()._draw_params(dev)
        bound = self.head_dim ** -0.5
        for li in range(self.num_layers):
            attn = getattr(self.blocks, f"l{li}").attn
            for p in (attn.phi, attn.mu):
                p.data = jnp.clip(p.data, -bound, bound)

    def _tree(self, leaf):
        """The tree every program receives, from `leaf(dotted name)`."""
        blocks = []
        for li in range(self.num_layers):
            pre = f"blocks.l{li}."
            blocks.append({
                "ln1": leaf(pre + "ln1.g"),
                "op": {n: leaf(pre + "attn." + n)
                       for n in ("W_qkv", "phi", "mu", "W_o")},
                "ln2": leaf(pre + "ln2.g"),
                "ffn": {n: leaf(pre + "mlp." + n)
                        for n in ("W_g", "W_u", "W_d")}})
        return {"embed": leaf("embed.W"), "blocks": blocks,
                "ln_f": leaf("ln_f.g"), "head": leaf("head.W")}

    # -- what is not implemented, by mechanism -----------------------------
    def _shard_decode_params(self, params, mesh):
        raise NotImplementedError(
            "ChunkedAttnLM: the tensor-parallel shard path is not "
            "implemented: a window buffer and a summary list have no "
            "sharding rule")

    # -- the mathematics ---------------------------------------------------
    def _norm(self, h, g, dtype):
        """RMSNorm of the float32 stream with gain 1 + g, in `dtype`."""
        return self._rms(h, 1.0 + g).astype(dtype)

    def _stack(self, params, ids, pos, attend):
        """Embedding through the final norm for ids [B, S] at positions
        pos [B, S]. `attend(li, q, k, v, phi, mu)` takes a layer's
        rotated q and k and its v, each [B,S,H,D], keeps what its cache
        keeps and returns [B,S,H,D]. The residual stream is float32."""
        import jax.numpy as jnp

        prec = tensor.get_matmul_precision()
        B, S = ids.shape
        H, D = self.num_heads, self.head_dim
        dt = params["embed"].dtype
        h = params["embed"][ids].astype(jnp.float32)
        for li, blk in enumerate(params["blocks"]):
            op = blk["op"]
            x = self._norm(h, blk["ln1"], dt)
            q, k, v = (t.reshape(B, S, H, D) for t in jnp.split(
                jnp.matmul(x, op["W_qkv"], precision=prec), 3, -1))
            q = rope(q, pos, self.rope_theta, D)
            k = rope(k, pos, self.rope_theta, D)
            a = attend(li, q, k, v, op["phi"], op["mu"])
            h = h + jnp.matmul(a.reshape(B, S, H * D), op["W_o"],
                               precision=prec)
            h = h + dense_mlp(blk["ffn"], self._norm(h, blk["ln2"], dt), prec)
        return self._norm(h, params["ln_f"], dt)

    def _head(self, params, h):
        """Head 0's columns of the held [d, P * V] matrix: the next
        byte's logits, float32 on the way out."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("head"):
            return jnp.matmul(h, params["head"][:, :self.vocab_size],
                              precision=tensor.get_matmul_precision(),
                              preferred_element_type=jnp.float32)

    def _summaries(self, k, v, phi, mu):
        """(sk, sv) [B, n, H, D] of the n = S // C chunks complete in
        k, v [B, S, H, D] (rotated keys): a chunk's C positions pooled
        with weights softmax(k . phi), mu added to the pooled key."""
        import jax
        import jax.numpy as jnp

        B, S, H, D = k.shape
        n, C = S // self.chunk, self.chunk
        kc, vc = (t[:, :n * C].reshape(B, n, C, H, D).astype(jnp.float32)
                  for t in (k, v))
        a = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, phi,
                                      precision="highest"), 2)
        return ((jnp.einsum("bnch,bnchd->bnhd", a, kc, precision="highest")
                 + mu).astype(k.dtype),
                jnp.einsum("bnch,bnchd->bnhd", a, vc,
                           precision="highest").astype(v.dtype))

    def _prompts(self, params, ids, n_real):
        """The stack over prompts ids [B, S] at positions 0..S-1, a
        block of W positions at a time (a loop over the blocks: block
        w's queries see its own keys and the summaries the earlier
        blocks left in the carry, so of a long prompt nothing is ever
        held whole but its hidden states and its summaries). Only the
        blocks that hold a real position of some row run: the blocks a
        bucket padded behind the longest row cost nothing, and their
        hidden states are zero. Returns (hidden [B, S, d], and for
        each layer what a slab row takes of a prompt whose first
        n_real [B] positions are real: {"k", "v": [B, H, D, min(W, S)],
        the row's last real block, zero behind its last real position;
        "sk", "sv": [B, H, D, S // C], zero from chunk n_real // C
        on})."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..ops.pallas_kernels import block_attend

        prec = tensor.get_matmul_precision()
        B, S = ids.shape
        W, C = self.window, self.chunk
        H, D = self.num_heads, self.head_dim
        dt = params["embed"].dtype
        nb = -(-S // W)
        Lb = W if nb > 1 else S               # a block's positions
        n = Lb // C                           # and its whole chunks
        if nb * Lb > S:
            ids = jnp.pad(ids, ((0, 0), (0, nb * Lb - S)))
        last = (n_real - 1) // W                              # [B]
        blocks = ids.reshape(B, nb, Lb).swapaxes(0, 1)        # [nb, B, Lb]
        held = [jnp.zeros((B, size, H, D), dt)
                for size in (nb * n, nb * n, Lb, Lb)]

        def block(w, carry):
            rows, h = carry
            new = [list(c) for c in rows]

            def attend(li, q, k, v, phi, mu):
                sk, sv, kb, vb = rows[li]
                with jax.named_scope("attn_chunked"):
                    # heads before positions for the kernel; a prompt
                    # shorter than a chunk has no list: one entry, unseen
                    lists = (sk, sv) if n else (jnp.zeros_like(k[:, :1]),) * 2
                    a = block_attend(*(
                        t.transpose(0, 2, 1, 3) for t in (q, k, v, *lists)),
                        n * w, prec).transpose(0, 2, 1, 3)
                    mine = (w == last)[:, None, None, None]
                    new[li][2:] = [jnp.where(mine, k, kb),
                                   jnp.where(mine, v, vb)]
                with jax.named_scope("chunk_summary"):
                    if n:
                        new[li][:2] = [
                            lax.dynamic_update_slice_in_dim(all_, one, n * w,
                                                            axis=1)
                            for all_, one in zip((sk, sv), self._summaries(
                                k, v, phi, mu))]
                return a

            pos = w * Lb + jnp.broadcast_to(jnp.arange(Lb), (B, Lb))
            h_w = self._stack(params, lax.dynamic_index_in_dim(
                blocks, w, 0, keepdims=False), pos, attend)
            return new, lax.dynamic_update_index_in_dim(h, h_w, w, 0)

        rows, h = lax.fori_loop(
            0, jnp.max(last) + 1, block,
            ([list(held) for _ in range(self.num_layers)],
             jnp.zeros((nb, B, Lb, self.d_model), dt)))
        whole = (jnp.arange(nb * n)[None, :]
                 < (n_real // C)[:, None])[:, :, None, None]
        kept = (jnp.arange(Lb)[None, :]
                <= (n_real - 1 - W * last)[:, None])[:, :, None, None]

        def lie(t, keep):   # [B, n, H, D] -> [B, H, D, n], zero past `keep`
            return jnp.where(keep, t, 0).transpose(0, 2, 3, 1)

        return (h.swapaxes(0, 1).reshape(B, nb * Lb, -1)[:, :S],
                [{"k": lie(kb, kept), "v": lie(vb, kept),
                  "sk": lie(sk, whole)[..., :S // C],
                  "sv": lie(sv, whole)[..., :S // C]}
                 for sk, sv, kb, vb in rows])

    # -- eval forward --------------------------------------------------------
    def _eval_logits(self, params, ids):
        import jax.numpy as jnp

        h, _ = self._prompts(params, ids,
                             jnp.full(ids.shape[:1], ids.shape[1]))
        return self._head(params, h)

    # -- the slab: summaries beside window buffers ---------------------------
    def new_slab(self, params, slots, seq, device):
        """Per layer {"k", "v": [slots, H, D, W]} and {"sk", "sv":
        [slots, H, D, seq / C]} (one entry where the rung is shorter
        than a chunk)."""
        import jax.numpy as jnp

        dtype = params["embed"].dtype
        H, D = self.num_heads, self.head_dim
        R = max(seq // self.chunk, 1)

        def zeros(n):
            return jnp.zeros((slots, H, D, n), dtype, device=device)

        return [{"k": zeros(self.window), "v": zeros(self.window),
                 "sk": zeros(R), "sv": zeros(R)}
                for _ in range(self.num_layers)]

    def grow_slab(self, slab, new_seq):
        """Only the summary lists grow; a buffer is left alone."""
        import jax.numpy as jnp

        more = new_seq // self.chunk - slab[0]["sk"].shape[3]
        return [{n: (jnp.pad(a, ((0, 0),) * 3 + ((0, more),))
                     if n in ("sk", "sv") else a) for n, a in c.items()}
                for c in slab]

    def slab_dims(self, slab):
        sk = slab[0]["sk"]
        return int(sk.shape[0]), int(sk.shape[3]) * self.chunk

    @staticmethod
    def slab_bytes(slab):
        out = {"window": 0, "summary": 0}
        for c in slab:
            for n, a in c.items():
                out["window" if n in ("k", "v") else "summary"] += (
                    a.size * a.dtype.itemsize)
        return out

    # -- the programs' step functions --------------------------------------
    def _seen_summaries(self, pos):
        """How many of a row's summaries its query at `pos` sees: those
        of the blocks before its own."""
        return (self.window // self.chunk) * (pos // self.window)

    def _slot_step(self, params, slab, tok, pos):
        """One fused decode step over every slot at per-row positions.
        Row b writes its key and value at pos[b] mod W of its buffer
        (`cache_write`), attends the buffer's entries 0 .. pos[b] mod W
        and the first `_seen_summaries(pos[b])` entries of its list
        (those of the earlier blocks) in one softmax, and, where its
        chunk closes ((pos[b] + 1) mod C == 0), pools the buffer's last
        C entries into summary pos[b] // C. The attention reads of a
        row the 128-entry blocks that hold those entries and masks a
        block's tail entry by entry (`window_summary_attend`) wherever
        `window_summary_blocks` cuts the slab's buffer and list into
        blocks; elsewhere two `einsum`s read both whole. Returns
        (logits [B, V], new slab, counters [4]: entries needed, held,
        summaries written, entries read)."""
        import jax
        import jax.numpy as jnp

        from ..ops.pallas_kernels import (cache_write, chunk_summary,
                                          window_summary_attend,
                                          window_summary_blocks,
                                          window_summary_entries_read)

        prec = tensor.get_matmul_precision()
        W, C = self.window, self.chunk
        R = slab[0]["sk"].shape[3]
        at, closes = pos % W, (pos + 1) % C == 0
        seen = self._seen_summaries(pos)
        blocks, _ = window_summary_blocks(W, R)
        new = [None] * len(slab)

        def attend(li, q, k, v, phi, mu):
            c = slab[li]
            with jax.named_scope("attn_chunked"):
                k_all = cache_write(c["k"], k[:, 0], at, axis=3)
                v_all = cache_write(c["v"], v[:, 0], at, axis=3)
                if blocks:
                    o = window_summary_attend(q[:, 0], k_all, v_all, c["sk"],
                                              c["sv"], at, seen)
                else:
                    s = jnp.concatenate([
                        jnp.einsum("bhd,bhdt->bht", q[:, 0], t,
                                   precision=prec,
                                   preferred_element_type=jnp.float32)
                        for t in (k_all, c["sk"])], -1) / float(
                            np.sqrt(self.head_dim))
                    mask = jnp.concatenate([
                        jnp.arange(W)[None, :] <= at[:, None],
                        jnp.arange(R)[None, :] < seen[:, None]], -1)
                    p = softmax_probs(s, mask[:, None, :],
                                      None).astype(v.dtype)
                    o = sum(jnp.einsum("bht,bhdt->bhd", pt, t, precision=prec,
                                       preferred_element_type=jnp.float32)
                            for pt, t in ((p[..., :W], v_all),
                                          (p[..., W:], c["sv"])))
            with jax.named_scope("chunk_summary"):
                sk, sv = chunk_summary(
                    k_all, v_all, c["sk"], c["sv"], phi, mu, at,
                    jnp.minimum(pos // C, R - 1), closes, per=C)
            new[li] = {"k": k_all, "v": v_all, "sk": sk, "sv": sv}
            return o.astype(v.dtype)[:, None]

        h = self._stack(params, tok[:, None], pos[:, None], attend)
        L = self.num_layers
        held = pos.shape[0] * (W + R)
        read = window_summary_entries_read(at, seen, W, R) if blocks else held
        counters = jnp.stack([
            L * jnp.sum(at + 1 + seen), jnp.asarray(L * held),
            L * jnp.sum(closes), L * read]).astype(jnp.int32)
        return self._head(params, h[:, 0]), new, counters

    def _prefill_rows(self, params, slab, ids, n_real, slots):
        """A cohort of bucket-padded prompts [Bp, Pb] through the
        stack, their state written into slab rows `slots` (a row whose
        slot is out of bounds writes nothing). Of a row with n real
        positions the summary list takes chunks 0 .. n // C - 1 and
        zeros up to the bucket's Pb / C, and the buffer the row's last
        real block, positions W * ((n - 1) // W) .. n - 1 at j mod W,
        zeros behind them: the pad tail lands nowhere, whatever the
        bucket's length, and what is left of the slot's last session
        lies where no query sees it before a step has written it."""
        import jax.numpy as jnp

        h, rows = self._prompts(params, ids, n_real)
        new = [{n: (put_rows(a, row[n], slots) if row[n].shape[3] else a)
                for n, a in c.items()} for c, row in zip(slab, rows)]
        last_h = jnp.take_along_axis(
            h, (n_real - 1)[:, None, None], axis=1)[:, 0]
        return self._head(params, last_h), new


def create_model(vocab_size=320, **kwargs):
    return ChunkedAttnLM(vocab_size, **kwargs)
