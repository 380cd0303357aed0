"""Decoder-only transformer LM — the multi-chip flagship.

No reference equivalent (SINGA's only transformer is the SONNX-imported
BERT, examples/onnx/bert); this model exists to exercise every
parallelism axis natively:

  * DP   — batch dim over "data" (mesh-mode `Model.compile`);
  * TP   — q/k/v/o and MLP GEMMs sharded over "model" via the default
           `parallel.ShardingRules` (Megatron-style column parallel);
  * SP   — ring attention over "seq" (parallel/ring_attention.py):
           sequence length scales with the number of chips;
all inside one jit-ed train step where XLA inserts the ICI collectives.
"""
from __future__ import annotations

import numpy as np

from .. import autograd, layer, quant as quant_mod, tensor
from .decode_lm import DecodeLM


_NORM_CLS = {"layer": layer.LayerNorm, "rms": layer.RMSNorm}


def _norm_cls(norm: str):
    try:
        return _NORM_CLS[norm]
    except KeyError:
        raise ValueError(
            f"norm must be one of {sorted(_NORM_CLS)}, got {norm!r}"
        ) from None


class TransformerBlock(layer.Layer):
    """Pre-norm block: x + MHA(LN(x)); x + MLP(LN(x))."""

    def __init__(self, num_heads: int, d_ff: int, causal: bool = True,
                 mesh=None, dropout: float = 0.0, norm: str = "layer",
                 name=None):
        super().__init__(name)
        norm_cls = _norm_cls(norm)
        self.ln1 = norm_cls()
        self.attn = layer.MultiHeadAttention(num_heads, causal=causal,
                                             mesh=mesh, dropout=dropout)
        self.ln2 = norm_cls()
        self.fc1 = layer.Linear(d_ff)
        self.act = layer.Gelu()
        self.fc2 = layer.Linear(0)  # lazily sized to d_model
        self.drop = layer.Dropout(dropout) if dropout else None

    def initialize(self, x):
        self.fc2.num_output = x.shape[-1]

    def forward(self, x):
        x = autograd.add(x, self.attn(self.ln1(x)))
        h = self.fc2(self.act(self.fc1(self.ln2(x))))
        if self.drop is not None:
            h = self.drop(h)
        return autograd.add(x, h)


def rung_attend(layer, q, pos, scale, prec):
    """One query a row against a slab layer [2, B, H, D, T] over the
    WHOLE rung: row b (at position pos[b]) attends slots j <= pos[b],
    the rest masked. What `decode_attend` computes from the row's own
    blocks, and its plain reference."""
    import jax
    import jax.numpy as jnp

    mask = pos[:, None] >= jnp.arange(layer.shape[-1])[None, :]
    neg = jnp.asarray(jnp.finfo(q.dtype).min / 2, q.dtype)
    s = jnp.einsum("bhd,bhdk->bhk", q, layer[0], precision=prec) * scale
    s = jnp.where(mask[:, None], s, neg)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhdk->bhd", p, layer[1], precision=prec)


class TransformerLM(DecodeLM):
    """Causal LM over int token ids [B, S] → logits [B, S, vocab]."""

    # what `_slot_step` counts a step, summed over rows and layers: the
    # 128-position blocks `decode_attend` read, and those the rung
    # holds (0 and 0 where attention takes the `einsum`s)
    step_counter_names = ("attn_blocks_read", "attn_blocks_rung")

    def __init__(self, vocab_size: int, d_model: int = 256,
                 num_heads: int = 8, num_layers: int = 4,
                 d_ff: int | None = None, max_len: int = 1024,
                 mesh=None, dropout: float = 0.0,
                 tie_embeddings: bool = False, norm: str = "layer"):
        super().__init__()
        _norm_cls(norm)  # validate early, shared message
        d_ff = d_ff or 4 * d_model
        self.vocab_size = vocab_size
        self.max_len = max_len
        self.tie_embeddings = tie_embeddings
        self.norm = norm
        self.embed = layer.Embedding(vocab_size, d_model)
        self.pos_embed = layer.Embedding(max_len, d_model)
        self.blocks = layer.Sequential(*[
            TransformerBlock(num_heads, d_ff, causal=True, mesh=mesh,
                             dropout=dropout, norm=norm)
            for _ in range(num_layers)
        ])
        self.ln_f = _norm_cls(norm)()
        # tied: logits = h @ W_embed^T (gradients flow into the
        # embedding from both uses); untied: separate projection
        self.head = (None if tie_embeddings
                     else layer.Linear(vocab_size, bias=False))

    def forward(self, x):
        B, S = x.shape
        pos = tensor.from_numpy(np.arange(S, dtype=np.int32))
        if x.device is not None:
            pos = pos.to_device(x.device)
        h = autograd.add(self.embed(x), self.pos_embed(pos))
        h = self.blocks(h)
        h = self.ln_f(h)
        if self.tie_embeddings:
            return autograd.matmul(
                h, autograd.transpose(self.embed.W, (1, 0)))
        return self.head(h)

    def train_one_batch(self, x, y):
        out = self.forward(x)                      # [B, S, V]
        logits = autograd.reshape(out, (-1, self.vocab_size))
        labels = autograd.reshape(y, (-1,))
        loss = autograd.softmax_cross_entropy(logits, labels)
        self._optimizer.backward_and_update(loss)
        return out, loss


    # -- jitted KV-cache generation (inference path) --------------------
    #
    # TPU-native incremental decoding: a static-shape KV cache
    # [L, 2, B, H, P+max_new, D] plus a lax.scan decode loop, compiled
    # once. The math mirrors the training stack exactly (pre-norm
    # blocks, exact-erf gelu, 1/sqrt(D) attention scale); the parity
    # test pins greedy decode against full-context forward argmax.

    def _decode_params(self):
        import jax.numpy as jnp

        def lin(l):
            return (l.W.data, l.b.data if l.bias else None)

        def ln(l):
            # (g,) = RMSNorm, (g, b) = LayerNorm — tuple LENGTH is the
            # dispatch. Nothing but arrays in a jitted call's tree: jax
            # transfers a Python scalar leaf to the device on EVERY
            # call (25 eps floats cost 5 ms of each 6.6 ms enqueue on
            # the v5e), so eps is a constant of the traced program,
            # read from the layer (`_norm_eps`)
            if isinstance(l, layer.RMSNorm):
                return (l.gamma.data,)
            return (l.gamma.data, l.beta.data)

        blocks = []
        for blk in self.blocks._seq:
            a = blk.attn
            blocks.append({
                "ln1": ln(blk.ln1),
                "q": lin(a.q_proj), "k": lin(a.k_proj),
                "v": lin(a.v_proj), "o": lin(a.o_proj),
                "ln2": ln(blk.ln2),
                "fc1": lin(blk.fc1), "fc2": lin(blk.fc2),
            })
        if self.tie_embeddings:
            # memoize the transposed view per embedding buffer: a
            # fresh .T array every call would defeat the TP
            # shard-cache's leaf-identity check in generate()
            src = self.embed.W.data
            cached = getattr(self, "_tied_head", None)
            if cached is None or cached[0] is not src:
                self._tied_head = (src, jnp.asarray(src).T)
            head = self._tied_head[1]
        else:
            head = jnp.asarray(self.head.W.data)
        return {
            "embed": self.embed.W.data, "pos": self.pos_embed.W.data,
            "blocks": blocks,
            "ln_f": ln(self.ln_f),
            "head": head,
        }

    def _norm_eps(self):
        """Every norm layer's own `eps`, block by block (ln1, ln2) and
        `ln_f` last: the static half of the norm specs. The decode
        programs bake these in at trace time, so they ride every
        program-cache key — a changed `eps` traces a new program, it
        never runs a stale one. (The AOT store is keyed by
        `topology_fingerprint()`, which hashes each layer's `eps`.)"""
        return tuple((float(blk.ln1.eps), float(blk.ln2.eps))
                     for blk in self.blocks._seq) + (
                         float(self.ln_f.eps),)

    def _decode_params_quant(self):
        """Int8 view of `_decode_params()` (ISSUE 19): linear entries
        become length-3 (payload, scale, bias) tuples — tuple LENGTH
        is the dispatch, the `_ln` idiom — and embed/pos/head become
        (payload, scale) pairs with broadcast-shaped scales. Memoized
        on the fp32 leaf identities (the `_gen_shard_cache` contract):
        a training step between decodes invalidates the copy."""
        import jax
        import jax.numpy as jnp

        base = self._decode_params()
        leaf_ids = tuple(id(l) for l in
                         jax.tree_util.tree_leaves(base))
        cached = getattr(self, "_quant_params_cache", None)
        if cached is not None and cached[0] == leaf_ids:
            return cached[1]
        qp = quant_mod.quantize_decode_params(base)

        def pair(t):  # device-put payload/scale once, not per step
            return ((jnp.asarray(t[0]), jnp.asarray(t[1])) + t[2:]
                    if isinstance(t, tuple) else t)

        qp["embed"] = pair(qp["embed"])
        qp["pos"] = pair(qp["pos"])
        qp["head"] = pair(qp["head"])
        for blk in qp["blocks"]:
            for k in ("q", "k", "v", "o", "fc1", "fc2"):
                blk[k] = pair(blk[k])
        self._quant_params_cache = (leaf_ids, qp)
        return qp

    @staticmethod
    def _table(spec, idx):
        """Embedding-style lookup for either param form: a plain
        array, or a quantized (payload, scale) pair with per-row
        scales — gather both planes, dequantize in fp32."""
        import jax.numpy as jnp

        if isinstance(spec, tuple):
            q, s = spec
            return q[idx].astype(s.dtype) * s[idx]
        return spec[idx]

    @staticmethod
    def _head_matmul(last, head, prec):
        import jax.numpy as jnp

        if isinstance(head, tuple):
            q, s = head
            return jnp.matmul(last, q.astype(last.dtype),
                              precision=prec) * s
        return jnp.matmul(last, head, precision=prec)

    @staticmethod
    def _ln(x, spec, eps):
        """`spec` is the traced half (arrays), `eps` the static half
        (a Python float from `_norm_eps`, a constant of the program)."""
        import jax.numpy as jnp

        if len(spec) == 1:  # RMSNorm: (gamma,)
            g, = spec
            return x / jnp.sqrt(
                jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g
        g, b = spec
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * g + b

    def _stack_step(self, params, ids, cache, pos0, last_index=None):
        """Run S tokens (positions pos0..pos0+S-1) through the block
        stack, writing their K/V into `cache` at those slots and
        attending over every filled slot. Returns (last-token logits,
        new cache). Works for both prefill (S=P) and decode (S=1).
        `last_index` (traced scalar) selects which row's logits to
        return instead of the last — bucket-padded prefill reads the
        REAL last prompt token, not the pad tail."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        H = self.blocks._seq[0].attn.num_heads
        B, S = ids.shape
        # quantized stacked cache (ISSUE 19): (payload int8
        # [L,2,B,H,T,D], scale f32 [L,2,B,T]) instead of one fp32
        # array — tuple-ness is the dispatch, like the _ln specs
        qcache = isinstance(cache, tuple)
        if qcache:
            new_pay, new_sc = cache
            maxT = new_pay.shape[-2]
        else:
            new_cache = cache
            maxT = cache.shape[-2]
        h = self._table(params["embed"], ids) \
            + self._table(params["pos"], pos0 + jnp.arange(S))
        E = h.shape[-1]
        D = E // H
        scale = 1.0 / float(np.sqrt(D))
        # query i (absolute pos0+i) may attend cache slot j <= pos0+i
        mask = (pos0 + jnp.arange(S))[:, None] >= jnp.arange(maxT)[None, :]
        neg = jnp.asarray(jnp.finfo(h.dtype).min / 2, h.dtype)

        prec = tensor.get_matmul_precision()

        def lin(x, wb):
            if len(wb) == 3:  # quantized: (payload, scale, bias) —
                # dequant COMMUTES through the matmul (per-output-
                # channel scale), so accumulation is fp32 and the
                # fp32 weight copy is never materialised
                qw, ws, b = wb
                y = jnp.matmul(x, qw.astype(x.dtype),
                               precision=prec) * ws
            else:
                w, b = wb
                y = jnp.matmul(x, w, precision=prec)
            return y if b is None else y + b

        *blk_eps, eps_f = self._norm_eps()
        for li, blk in enumerate(params["blocks"]):
            eps1, eps2 = blk_eps[li]
            x = self._ln(h, blk["ln1"], eps1)

            def split(t):  # [B,S,E] -> [B,H,S,D]
                return t.reshape(B, S, H, D).transpose(0, 2, 1, 3)

            q = split(lin(x, blk["q"]))
            kk = split(lin(x, blk["k"]))
            vv = split(lin(x, blk["v"]))
            kv = jnp.stack([kk, vv])
            if qcache:
                # per-position scales (reduce over H, D ONLY — the
                # same extent the S=1 step uses, which is what makes
                # chunked replay bit-exact against per-step decode)
                qkv, sc = quant_mod.quantize_kv(kv)
                new_pay = lax.dynamic_update_slice(
                    new_pay, qkv[None], (li, 0, 0, 0, pos0, 0))
                new_sc = lax.dynamic_update_slice(
                    new_sc, sc[None], (li, 0, 0, pos0))
                kv_all = quant_mod.dequantize_kv(
                    lax.dynamic_index_in_dim(new_pay, li, 0,
                                             keepdims=False),
                    lax.dynamic_index_in_dim(new_sc, li, 0,
                                             keepdims=False))
                k_all, v_all = kv_all[0], kv_all[1]
            else:
                new_cache = lax.dynamic_update_slice(
                    new_cache, kv[None], (li, 0, 0, 0, pos0, 0))
                k_all = lax.dynamic_index_in_dim(new_cache, li, 0,
                                                 keepdims=False)[0]
                v_all = lax.dynamic_index_in_dim(new_cache, li, 0,
                                                 keepdims=False)[1]
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k_all,
                           precision=prec) * scale
            s = jnp.where(mask[None, None], s, neg)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, v_all, precision=prec)
            o = o.transpose(0, 2, 1, 3).reshape(B, S, E)
            h = h + lin(o, blk["o"])
            x = self._ln(h, blk["ln2"], eps2)
            h = h + lin(jax.nn.gelu(lin(x, blk["fc1"]),
                                    approximate=False), blk["fc2"])
        h = self._ln(h, params["ln_f"], eps_f)
        if last_index is None:
            last = h[:, -1]
        elif getattr(last_index, "ndim", 0) == 1:
            # per-row last index ([B] vector) — cohort prefill packs
            # sessions with different real prompt lengths into one
            # bucket-padded batch; each row reads ITS last real token
            last = jnp.take_along_axis(
                h, last_index[:, None, None], axis=1)[:, 0]
        else:
            last = lax.dynamic_index_in_dim(h, last_index, 1,
                                            keepdims=False)
        return (self._head_matmul(last, params["head"], prec),
                (new_pay, new_sc) if qcache else new_cache)

    def _compiled_decode(self, B, P, max_new, temperature, top_k):
        """Build (or fetch) the jitted prefill+scan decode program for
        this (shapes, sampling config) combination. Cached on the
        model so repeat generate() calls skip the XLA compile."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        key_ = (B, P, max_new, float(temperature), int(top_k),
                self._trace_key())  # baked in at trace time
        cache_dict = self._program_cache()
        hit = cache_dict.get(key_)
        if hit is not None:
            return hit

        def sample(logits, key):
            if temperature == 0.0:
                return jnp.argmax(logits, -1).astype(jnp.int32)
            z = logits / temperature
            if top_k > 0:
                k = min(top_k, int(logits.shape[-1]))
                kth = lax.top_k(z, k)[0][..., -1:]
                z = jnp.where(z < kth, -jnp.inf, z)
            return jax.random.categorical(key, z).astype(jnp.int32)

        @jax.jit
        def run(params, prompt, cache, key):
            logits, cache = self._stack_step(params, prompt, cache, 0)
            key, sub = jax.random.split(key)
            tok = sample(logits, sub)

            def step(carry, _):
                cache, tok, pos, key = carry
                logits, cache = self._stack_step(
                    params, tok[:, None], cache, pos)
                key, sub = jax.random.split(key)
                nxt = sample(logits, sub)
                return (cache, nxt, pos + 1, key), tok

            (_, last, _, _), toks = lax.scan(
                step, (cache, tok, jnp.int32(P), key), None,
                length=max_new - 1) if max_new > 1 else (
                (None, tok, None, None),
                jnp.zeros((0, B), jnp.int32))
            return jnp.concatenate([toks.T, last[:, None]], axis=1)

        run = self._count_first_trace(run)
        cache_dict[key_] = run
        return run

    # -- token-granularity decode tier (ISSUE 16) -----------------------
    #
    # generate() fuses prefill + the whole decode loop into one
    # program per request shape; a serving tier needs the OPPOSITE
    # factoring — ONE warm single-step executable shared by every
    # in-flight session, so sequences can join/leave the fused batch
    # between steps. decode_step / prefill_step / sample_fn are that
    # factoring, with the bit-identity contract: a session decoded
    # through the shared slab reproduces generate()'s exact token
    # stream (same logits bits, same key-split sequence).

    def _slot_step(self, params, cache, tok, pos):
        """One fused decode step over every batch slot at PER-ROW
        positions: row b writes its K/V at cache slot (b, pos[b]) and
        attends slots 0..pos[b]. `cache` is a PER-LAYER list of
        [2, B, H, D, T] arrays (`new_slab`): one buffer per layer, so
        a layer's write touches that layer alone, and positions LAST,
        which is how the chip lays a layer out whatever its shape
        says. Both rows of a step (key and value) go in through one
        `cache_write`, in place in the donated slab, and attention
        reads the layer as stored: no operation of the program moves a
        whole layer but that write (`tests/test_tpu_compile_widths.py`;
        in [2, B, H, T, D] the write was re-laid out and back, 24
        whole-layer copies a program). The mathematics is
        `_stack_step`'s at S=1 (same products over the same float32
        values, same mask constant), so a slab row decodes the same
        request's `generate()` stream.

        Which attention, from the slab alone: a float32 rung of
        several 128-position blocks goes through `decode_attend`,
        which reads of row b the blocks 0..pos[b] // 128 and no more
        (ISSUE 30: the sessions of `gpt2-serve-decode` hold a quarter
        of their rung); a rung of one block has nothing to skip, and
        an int8 slab is dequantized a whole layer at a time, so both
        keep the two `einsum`s over the rung.

        Returns (logits [B, V], new per-layer cache list, the step's
        `step_counter_names`: blocks read, blocks the rung holds)."""
        import jax
        import jax.numpy as jnp

        from ..ops.pallas_kernels import (DECODE_ATTEND_BLOCK, cache_write,
                                          decode_attend,
                                          decode_attend_blocks)

        H = self.blocks._seq[0].attn.num_heads
        B = tok.shape[0]
        # quantized slab (ISSUE 19): per-layer (payload int8
        # [2,B,H,D,T], scale f32 [2,B,T]) tuples instead of plain
        # fp32 arrays, written and read through the same code
        qcache = isinstance(cache[0], tuple)
        maxT = quant_mod.slab_payload(cache[0]).shape[-1]
        h = self._table(params["embed"], tok[:, None]) \
            + self._table(params["pos"], pos)[:, None]
        E = h.shape[-1]
        D = E // H
        scale = 1.0 / float(np.sqrt(D))
        at = jnp.concatenate([pos, pos])
        new_cache = []
        nblk = 0 if qcache else decode_attend_blocks(maxT, cache[0].dtype)

        prec = tensor.get_matmul_precision()

        def lin(x, wb):
            if len(wb) == 3:  # (payload, scale, bias): dequant-at-
                # use, fp32 accumulation — see _stack_step
                qw, ws, b = wb
                y = jnp.matmul(x, qw.astype(x.dtype),
                               precision=prec) * ws
            else:
                w, b = wb
                y = jnp.matmul(x, w, precision=prec)
            return y if b is None else y + b

        def write(layer, kv):
            # layer [2,B,H,D,T], kv [2,B,H,D]: keys are rows 0..B-1 of
            # the kernel's [2B,H,D,T] view, values rows B..2B-1
            return cache_write(layer.reshape((2 * B,) + layer.shape[2:]),
                               kv.reshape(2 * B, H, D), at,
                               axis=3).reshape(layer.shape)

        *blk_eps, eps_f = self._norm_eps()
        for li, blk in enumerate(params["blocks"]):
            eps1, eps2 = blk_eps[li]
            x = self._ln(h, blk["ln1"], eps1)
            q = lin(x, blk["q"]).reshape(B, H, D)
            kv = jnp.stack([lin(x, blk["k"]).reshape(B, H, D),
                            lin(x, blk["v"]).reshape(B, H, D)])
            if qcache:
                # same per-position quantization as the chunked
                # prefill form (reduce over H, D) — the replay
                # bit-exactness lever
                qkv, sc = quant_mod.quantize_kv(
                    kv[:, :, :, None, :])             # sc [2,B,1]
                payload, scp = cache[li]
                new_pay = write(payload, qkv[:, :, :, 0, :])
                new_sc = jnp.where(pos[:, None] == jnp.arange(maxT), sc,
                                   scp)
                new_cache.append((new_pay, new_sc))
                kv_all = quant_mod.dequantize_slab(new_pay, new_sc)
            else:
                kv_all = write(cache[li], kv)
                new_cache.append(kv_all)
            # row b (absolute position pos[b]) attends slots j <= pos[b]
            if nblk:
                o = decode_attend(kv_all, q, pos, scale)
            else:
                o = rung_attend(kv_all, q, pos, scale, prec)
            h = h + lin(o.reshape(B, 1, E), blk["o"])
            x = self._ln(h, blk["ln2"], eps2)
            h = h + lin(jax.nn.gelu(lin(x, blk["fc1"]),
                                    approximate=False), blk["fc2"])
        h = self._ln(h, params["ln_f"], eps_f)
        L = len(new_cache)
        counters = jnp.array(
            [L * jnp.sum(pos // DECODE_ATTEND_BLOCK + 1) if nblk else 0,
             L * B * nblk], jnp.int32)
        return (self._head_matmul(h[:, -1], params["head"], prec),
                new_cache, counters)

    def decode_step_hlo(self, params, cache, tok, pos,
                        optimized: bool = True) -> str:
        """HLO text of the fused decode step at this exact slab
        geometry — input to `hlo_profile.bytes_accessed`, the byte
        meter the int8 KV/weight diet is gated on (ISSUE 19): the
        quantized step must access STRICTLY fewer bytes than the
        fp32 step at the same geometry, post-XLA-optimization (so a
        convert that materializes the fp32 copy would fail the gate,
        not hide inside it)."""
        import jax

        jitted = jax.jit(lambda p, c, t, po: self._slot_step(p, c, t, po))
        lowered = jitted.lower(params, list(cache), tok, pos)
        return (lowered.compile().as_text() if optimized
                else lowered.as_text())

    @staticmethod
    def _slab_extra(cache):
        """Export-key extras fragment for a decode slab: shapes for
        the plain form, shapes + quant marker for the packed form —
        an int8 slab artifact must never be loaded for an fp32 slab
        (or vice versa)."""
        if quant_mod.is_quant_cache(cache):
            return {"quant": "int8",
                    "payload": [list(p.shape) for p, _ in cache],
                    "scale": [list(s.shape) for _, s in cache]}
        return [list(c.shape) for c in cache]

    def prefill_step(self, params, cache, ids, n_real):
        """Prefill one session's bucket-padded prompt: run `ids`
        [B, Pb] at positions 0..Pb-1, writing K/V into `cache`, and
        return (logits at row n_real-1 — the REAL last prompt token —
        [B, V], new cache). Pad rows beyond n_real do write K/V, but
        the causal mask hides them from every real prompt row and the
        decode steps overwrite slot p before any query can attend it,
        so bucketed prefill is exact, not approximate. Compiled once
        per (Pb, slab) shape; AOT-exported like decode_step."""
        import jax.numpy as jnp

        cache_dict = self._program_cache()
        key_ = ("prefill", ids.shape, cache.shape,
                jnp.asarray(cache).dtype.name, self._trace_key())
        fn = cache_dict.get(key_)
        if fn is None:
            import jax

            jitted = jax.jit(
                lambda p, c, i, n: self._stack_step(
                    p, i, c, 0, last_index=n - 1))
            args = (params, cache, ids, n_real)
            fn = self._aot_step(
                "prefill_step", jitted, args,
                extras={"prompt_bucket": list(ids.shape),
                        "slab": list(cache.shape),
                        "policy": autograd._policy_key()})
            cache_dict[key_] = fn
        return fn(params, cache, ids, n_real)

    def _prefill_rows(self, params, slab, ids, n_real, slots):
        """`prefill_slab`'s program: `_stack_step` runs `ids` [Bp, Pb]
        against a fresh Pb-wide cache materialised in-graph, and every
        layer's rows land in the donated slab, in place, via one
        scatter (a row whose slot is out of bounds is dropped by it).
        Only the cohort's rows are turned positions-last on the way.
        The slab keeps its stale tail beyond Pb; decode overwrites
        position p before any query attends it (see `prefill_step`'s
        pad argument)."""
        import jax
        import jax.numpy as jnp

        L = len(slab)
        qslab = quant_mod.is_quant_cache(slab)
        c0 = quant_mod.slab_payload(slab[0])
        H, D = int(c0.shape[2]), int(c0.shape[3])
        Bp, Pb = ids.shape
        # fresh Pb-wide cache in-graph, in the slab's form: the
        # chunked _stack_step writes the same payload + scale planes
        # the per-step chain would (see quantize_kv)
        c1 = jnp.zeros((L, 2, Bp, H, Pb, D), c0.dtype)
        if qslab:
            c1 = (c1, jnp.zeros((L, 2, Bp, Pb), jnp.float32))
        logits, c1 = self._stack_step(params, ids, c1, 0,
                                      last_index=n_real - 1)
        rows = jnp.swapaxes(c1[0] if qslab else c1, -1, -2)
        if qslab:
            rows = (rows, c1[1])
        # leaf by leaf (a layer, or its payload and its scales): the
        # cohort's first Pb positions of rows `slots`
        return logits, [
            jax.tree_util.tree_map(
                lambda a, r: a.at[:, slots, ..., :Pb].set(r[li]),
                slab[li], rows)
            for li in range(L)]

    # -- the slab, as serve.py asks for it -------------------------------
    _slab_sig = staticmethod(quant_mod.cache_sig)

    def new_slab(self, params, slots, seq, device):
        """A per-layer list of [2, slots, H, D, seq] buffers, keys at
        [0] and values at [1], positions last (`_slot_step` says why;
        one buffer per layer, so that a write touches one layer),
        born on `device`; for int8 params the (payload, scale
        [2, slots, seq]) form in the same geometry."""
        import jax.numpy as jnp

        quant = isinstance(params["embed"], tuple)
        embed = params["embed"][0] if quant else params["embed"]
        H = self.blocks._seq[0].attn.num_heads
        shape = (2, slots, H, int(embed.shape[-1]) // H, seq)

        def layer():
            if quant:
                return (jnp.zeros(shape, jnp.int8, device=device),
                        jnp.zeros((2, slots, seq), jnp.float32,
                                  device=device))
            return jnp.zeros(shape, embed.dtype, device=device)

        return [layer() for _ in params["blocks"]]

    grow_slab = staticmethod(quant_mod.pad_slab_seq)

    @staticmethod
    def slab_dims(slab):
        """(slots, sequence rung) of either slab form."""
        s0 = quant_mod.slab_shape(slab)
        return int(s0[1]), int(s0[4])

    @staticmethod
    def slab_bytes(slab):
        """Every layer holds the context: nothing here is a ring."""
        import jax

        return {"ring": 0, "context": sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(slab))}

    def export_slab_rows(self, slab, slot, pos):
        """Snapshot one session's live K/V out of the decode slab as a
        single host array [L, 2, H, pos, D] — the portable half of KV
        migration, and the wire form whatever the slab's own layout
        (transposed on the host, here). Pure host-side gather (no
        compile): the slab leaves are device arrays, `np.asarray`
        forces the transfer, and only the first `pos` positions are
        real (the tail past `pos` is stale garbage decode would
        overwrite anyway, so it never crosses the wire). A QUANTIZED
        slab exports the PACKED form — (payload int8
        [L, 2, H, pos, D], scale f32 [L, 2, pos]) — so live migration
        ships ~4x fewer bytes (ISSUE 19)."""
        if quant_mod.is_quant_cache(slab):
            quant_mod.stats_counters()["packed_kv_exports"] += 1
            return quant_mod.pack_slab_rows(slab, slot, pos)
        return quant_mod.rows_to_wire(slab, slot, pos)

    def import_slab_rows(self, slab, slot, rows):
        """Transplant `export_slab_rows` output into row `slot` of a
        (possibly different-geometry) slab, in place in the donated
        slab, returning the new slab. The rows are turned positions-
        last and zero-padded host-side to the target's rung so ONE
        executable per slab geometry serves every (slot, pos) pair —
        `slot` is traced, and the stale-tail argument from
        `prefill_slab` makes the zero padding exact: decode overwrites
        position p before any query attends it. Requires the target
        rung to cover `pos` (serve sizes the rung from the session's
        own prompt+budget, which migration preserves). A QUANTIZED
        slab takes the PACKED pair `export_slab_rows` produced —
        (payload, scale) — and transplants both planes; mixing forms
        (packed rows into an fp32 slab or vice versa) raises, before
        anything is dispatched."""
        import jax

        L = len(slab)
        qslab = quant_mod.is_quant_cache(slab)
        qrows = isinstance(rows, tuple)
        if qslab != qrows:
            raise ValueError(
                f"KV form mismatch: slab is "
                f"{'int8-packed' if qslab else 'fp32'} but rows are "
                f"{'int8-packed' if qrows else 'fp32'} — the quant "
                "mode must match across a migration (it rides the "
                "fleet spec and knob_fingerprint)")
        c0 = quant_mod.slab_payload(slab[0])
        H, D, Ts = (int(n) for n in c0.shape[2:])
        pay = rows[0] if qslab else rows
        t = int(pay.shape[3])
        if pay.shape[0] != L or pay.shape[2] != H \
                or pay.shape[4] != D or t > Ts:
            raise ValueError(
                f"KV rows {tuple(pay.shape)} do not fit slab "
                f"[L={L}, H={H}, T={Ts}, D={D}]")
        cache_dict = self._program_cache()
        key_ = ("import_slab", quant_mod.cache_sig(slab))
        fn = cache_dict.get(key_)
        if fn is None:
            def put_rows(sl, r, s):
                # leaf by leaf: a layer, or its payload and its scales
                return [jax.tree_util.tree_map(
                    lambda a, x: a.at[:, s].set(x[li]), layer, r)
                    for li, layer in enumerate(sl)]

            fn = jax.jit(put_rows, donate_argnums=(0,))
            cache_dict[key_] = fn
        padded = quant_mod.rows_from_wire(
            np.asarray(pay, c0.dtype), Ts)
        if qslab:
            psc = np.zeros((L, 2, Ts), np.float32)
            psc[:, :, :t] = rows[1]
            padded = (padded, psc)
        return fn(list(slab), padded, np.int32(slot))

    def _shard_decode_params(self, params, mesh):
        """Lay the decode params out for tensor-parallel inference on
        `mesh` ("model" axis): q/k/v and fc1 column-parallel, o and
        fc2 row-parallel, head column-parallel over vocab —
        Megatron's split (parallel/sharding.py). GSPMD then partitions
        the whole prefill+scan program, inserting the collectives."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.sharding import _validate

        def put(x, spec):
            # _validate degrades to replicated when the mesh lacks the
            # axis or the axis size doesn't divide the dim — same
            # fallback the training-path ShardingRules applies
            spec = _validate(mesh, spec, x.shape)
            return jax.device_put(x, NamedSharding(mesh, spec))

        col, row, rep = P(None, "model"), P("model", None), P()

        def norm_put(t):  # nothing but arrays in a jitted call's tree
            return tuple(put(v, rep) for v in t)

        def lin(wb, spec):
            w, b = wb
            bspec = (P("model") if spec is col else P())
            return (put(w, spec), None if b is None else put(b, bspec))

        out = {"embed": put(params["embed"], rep),
               "pos": put(params["pos"], rep),
               "ln_f": norm_put(params["ln_f"]),
               "head": put(params["head"], col), "blocks": []}
        for blk in params["blocks"]:
            out["blocks"].append({
                "ln1": norm_put(blk["ln1"]),
                "q": lin(blk["q"], col), "k": lin(blk["k"], col),
                "v": lin(blk["v"], col), "o": lin(blk["o"], row),
                "ln2": norm_put(blk["ln2"]),
                "fc1": lin(blk["fc1"], col), "fc2": lin(blk["fc2"], row),
            })
        return out

    def generate(self, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 mesh=None):
        """Autoregressively extend `prompt_ids` [B, P] (numpy int) by
        `max_new_tokens`. temperature=0 → greedy; otherwise softmax
        sampling, optionally truncated to the `top_k` highest logits
        (clamped to the vocab size). The prefill + lax.scan decode
        loop is compiled once per (shape, sampling config) and cached
        on the model. With `mesh` (a jax Mesh with a "model" axis) the
        params are laid out Megatron-style and GSPMD partitions the
        decode across the chips (tensor-parallel inference).

        Precision: decode computes in the PARAM dtype under the
        matmul-precision policy (`tensor.set_matmul_precision` — use
        "default" for bf16 MXU passes, the main inference speed
        lever); the AMP compute-dtype policy is a training-path
        activation policy and is deliberately not applied here, so
        greedy decode stays exactly consistent with the fp32 eval
        forward. Returns numpy [B, P + max_new_tokens]."""
        import jax
        import jax.numpy as jnp

        prompt_ids = np.asarray(prompt_ids, np.int32)
        if max_new_tokens < 0:
            raise ValueError(f"max_new_tokens must be >= 0, "
                             f"got {max_new_tokens}")
        if max_new_tokens == 0:
            return prompt_ids.copy()
        B, P = prompt_ids.shape
        T = P + max_new_tokens
        if T > self.max_len:
            raise ValueError(f"P+new = {T} exceeds max_len {self.max_len}")
        params = self._decode_params()
        if mesh is not None:
            # memoized per mesh: re-putting the whole tree per call
            # would pay a full-model reshard each generate(). Keyed on
            # the live leaf identities so a training step between
            # decodes invalidates the copy (stale weights otherwise).
            shard_cache = getattr(self, "_gen_shard_cache", None)
            if shard_cache is None:
                shard_cache = self._gen_shard_cache = {}
            leaf_ids = tuple(id(l) for l in
                             jax.tree_util.tree_leaves(params))
            hit = shard_cache.get(id(mesh))
            if hit is None or hit[0] != leaf_ids:
                shard_cache[id(mesh)] = (
                    leaf_ids, self._shard_decode_params(params, mesh))
            params = shard_cache[id(mesh)][1]
        L = len(params["blocks"])
        H = self.blocks._seq[0].attn.num_heads
        D = params["embed"].shape[-1] // H
        # cache seq dim rounded up to a power of two, NOT the exact
        # T = P + max_new: pow2 reduction widths are mutually bitwise
        # stable on XLA CPU (trailing masked slots contribute exact
        # zeros in identical lane order), which is what lets the
        # serving tier's shared decode slab (any pow2 >= T) reproduce
        # generate()'s streams bit-for-bit. Odd widths vectorize with
        # a remainder tail and drift in the last ulp. Not max_len:
        # every decode step still attends only ~T slots.
        t_alloc = 1 << (T - 1).bit_length()
        cache = jnp.zeros((L, 2, B, H, t_alloc, D),
                          params["embed"].dtype)
        run = self._compiled_decode(B, P, max_new_tokens, temperature,
                                    top_k)
        new = np.asarray(run(params, jnp.asarray(prompt_ids), cache,
                             jax.random.PRNGKey(seed)))
        return np.concatenate([prompt_ids, new], axis=1)


def create_model(vocab_size=256, **kwargs):
    return TransformerLM(vocab_size, **kwargs)
