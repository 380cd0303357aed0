"""A hybrid linear-attention mixture-of-experts LM (`model_type`
`qwen3_next`): three Gated DeltaNet layers to one gated full-attention
layer, every layer's MLP routed beside a gated shared expert, served
as ONE chip's share of an expert-parallel deployment. No bias
anywhere.

    N(h, g) = h / sqrt(mean(h^2) + eps) * (1 + g)          float32
    h += Mixer_l(N(h, ln1));   h += MoE_l(N(h, ln2))
    layer l is full attention iff (l + 1) % full_interval == 0
    full:  [q | gate] = x W_q per head (D each), k = x W_k, v = x W_v;
           q, k = N over each head's D, THEN rotary over the first
           `rotary_dim` dims (rotate-half); causal softmax(q.k / sqrt(D))
           v, grouped;  Mixer = (o * sigmoid(gate)) W_o
    Gated DeltaNet (Hk key heads, Hv value heads of Dk and Dv):
           x W_qkvz is Hk groups of [q Dk | k Dk | v r Dv | z r Dv],
           x W_ba Hk groups of [b r | a r], r = Hv / Hk (v, z, b, a
           then read as Hv heads: head h is in group h // r)
           c = silu(causal depthwise conv_L(q | k | v))     no bias
           q, k: group h // r's, l2-normed (eps 1e-6), q / sqrt(Dk)
           beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
           S <- exp(g) S;  S <- S + k (x) beta (v - S^T k);  o = S^T q
           Mixer = concat_heads(rms(o) * w * silu(z)) W_out    w plain
    MoE:   `routed_experts` with softmax scores over all experts, top-k
           renormalised;  + sigmoid(x w_s) (silu(x G_s) * x U_s) D_s
    logits = N(h_last, ln_f) W_head

`held = (first, count)` names the routed experts this chip holds; the
router keeps its full width and the shared expert is computed whole on
every chip. One functional stack (`_stack`) serves `forward()` in eval
mode, the cohort prefill and the fused decode step; there is no
backward, so `train_one_batch` raises. The residual stream is float32.

The slab holds three kinds of entry. A Gated DeltaNet layer keeps a
RECURRENT state, {"S": [slots, Hv, Dk, Dv]} float32, the matrix state
of each value head, and a convolution STATE, {"u": [slots, L-1, C]}:
the last L-1 convolution inputs, u_p in column p mod (L-1)
(`drawn_lm.conv_step`), its channels as the projection groups them,
[q | k | v] a key head. Neither grows with the rung; a decode step
updates both in place in the donated slab (the states through the
`gated_delta_step` kernel, each row's once in and once out; the
convolution by a select over the oldest column), and a prefill writes
ALL of both from the row's real positions alone (a padded position has
g = 0 and beta = 0, so it leaves S as it was), so a slot that takes a
new session keeps nothing of the last one. A full layer keeps the
CONTEXT, keys and values alike [slots, Hkv, D, T] with positions last,
climbing the sequence ladder. A prompt runs the delta rule's chunked
form (`chunk_gated_delta`), its algebra in float32 at HIGHEST. The step
counts the rows whose states it advanced (`gdn_state_rows`: every
slot, times the Gated DeltaNet layers).
"""
from __future__ import annotations

import numpy as np

from .. import tensor
from .drawn_lm import (RoutedDrawnLM, attend_cache, attend_prompts,
                       conv_columns, conv_prompt, conv_step, conv_tail,
                       conv_tail_positions, put_rows, rope)

LINEAR, FULL = "linear_attention", "full_attention"
CHUNK = 64      # positions a chunk of a prompt's delta rule (published C)


def unit_lower_inverse(a):
    """(I + a)^-1 for a [..., C, C] strictly lower triangular, C a
    power of two: the inverse's diagonal blocks doubled from 1 x 1 up,
    each new off-diagonal block -inv22 a21 inv11 (block forward
    substitution, two products a level at HIGHEST: a triangular solve
    leaves the precision of its products to the backend)."""
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    C = a.shape[-1]
    if C & (C - 1):
        raise ValueError(f"unit_lower_inverse: {C} is no power of two")
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (C, 1, 1), a.dtype)
    b = 1
    while b < C:
        n = C // (2 * b)
        blocks = jnp.diagonal(a.reshape(lead + (n, 2 * b, n, 2 * b)),
                              axis1=-4, axis2=-2)          # [..., 2b, 2b, n]
        a21 = jnp.moveaxis(blocks, -1, -3)[..., b:, :b]    # [..., n, b, b]
        pair = inv.reshape(lead + (n, 2, b, b))
        i11, i22 = pair[..., 0, :, :], pair[..., 1, :, :]
        off = -jnp.matmul(jnp.matmul(i22, a21, precision=hi), i11,
                          precision=hi)
        inv = jnp.concatenate(
            [jnp.concatenate([i11, jnp.zeros_like(i11)], -1),
             jnp.concatenate([off, i22], -1)], -2)
        b *= 2
    return inv.reshape(lead + (C, C))


def chunk_gated_delta(q, k, v, g, beta, state, chunk):
    """The gated delta rule over positions 0..L-1, `chunk` at a time
    (the UT form: Yang et al., arXiv:2406.06484 section 3, with the
    gate of arXiv:2412.06464): q, k [B, H, L, Dk], v [B, H, L, Dv], g
    and beta [B, H, L] (float32), the state before [B, H, Dk, Dv]
    float32 -> (o [B, H, L, Dv], the state after L). Within a chunk,
    G_i = sum_{j<=i} g_j and D_ij = exp(G_i - G_j) for j <= i:

        T  = (I + strictly_lower(diag(beta) K K^T * D))^-1
        U  = T (beta * V);   W = T (beta * exp(G) * K)
        V' = U - W S;   O = (exp(G) * Q) S + lower(Q K^T * D) V'
        S  <- exp(G_C) S + (K * exp(G_C - G))^T V'

    A position with g = 0 and beta = 0 leaves S as it was. Every
    product at HIGHEST; L is padded to whole chunks so."""
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    B, H, L, _ = q.shape
    C = chunk
    pad = -L % C
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for t in (q, k, v))
        g, beta = (jnp.pad(t, ((0, 0), (0, 0), (0, pad))) for t in (g, beta))
    n = (L + pad) // C

    def chunks(t):
        return t.reshape((B, H, n, C) + t.shape[3:])

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    G = jnp.cumsum(g, -1)                                  # [B, H, n, C]
    i = jnp.arange(C)
    lower = i[:, None] >= i[None, :]
    D = jnp.where(lower, jnp.exp(jnp.where(
        lower, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    kb = k * beta[..., None]
    A = jnp.where(i[:, None] > i[None, :], jnp.einsum(
        "...id,...jd->...ij", kb, k, precision=hi) * D, 0.0)
    T = unit_lower_inverse(A)
    U = jnp.matmul(T, v * beta[..., None], precision=hi)
    W = jnp.matmul(T, kb * jnp.exp(G)[..., None], precision=hi)
    P = jnp.einsum("...id,...jd->...ij", q, k, precision=hi) * D
    Qd = q * jnp.exp(G)[..., None]
    Kd = k * jnp.exp(G[..., -1:] - G)[..., None]
    last = jnp.exp(G[..., -1])[..., None, None]           # [B, H, n, 1, 1]

    def step(S, xs):
        U, W, P, Qd, Kd, last = xs
        Vp = U - jnp.matmul(W, S, precision=hi)
        O = (jnp.matmul(Qd, S, precision=hi)
             + jnp.matmul(P, Vp, precision=hi))
        S = last * S + jnp.einsum("...ik,...iv->...kv", Kd, Vp,
                                  precision=hi)
        return S, O

    S, O = lax.scan(step, state, tuple(
        jnp.moveaxis(t, 2, 0) for t in (U, W, P, Qd, Kd, last)))
    O = jnp.moveaxis(O, 0, 2).reshape(B, H, n * C, -1)[:, :, :L]
    return O, S


class GatedDeltaMoELM(RoutedDrawnLM):
    """Causal LM over int token ids [B, S] -> logits [B, S, vocab]."""

    step_counter_names = RoutedDrawnLM.step_counter_names + (
        "gdn_state_rows",)
    router_scoring = "softmax"
    _slab_words = ("recurrent matrix states a value head, convolution "
                   "states and contexts")
    _training_lacks = ("with no backward for the gated delta rule's chunked "
                       "form, the short convolution or the routed experts, "
                       "and no optimizer state for a share of the experts")

    def __init__(self, vocab_size: int, d_model: int = 2048,
                 num_layers: int = 8, full_interval: int = 4,
                 num_heads: int = 16, kv_heads: int = 2, head_dim: int = 256,
                 rotary_dim: int = 64, rope_theta: float = 1e7,
                 lin_k_heads: int = 16, lin_v_heads: int = 32,
                 lin_k_dim: int = 128, lin_v_dim: int = 128,
                 conv_L: int = 4, d_ff_expert: int = 512,
                 d_ff_shared: int = 512, n_experts: int = 512,
                 experts_per_token: int = 10, held=(0, 32),
                 norm_eps: float = 1e-6, max_len: int = 4096,
                 param_dtype: str = "float32", init_std: float = 0.02):
        super().__init__()
        if num_heads % kv_heads or rotary_dim % 2 or rotary_dim > head_dim:
            raise ValueError(f"{num_heads} heads over {kv_heads} groups, "
                             f"rotary_dim {rotary_dim} of {head_dim}")
        if lin_v_heads % lin_k_heads or conv_L < 2:
            raise ValueError(f"{lin_v_heads} value heads over {lin_k_heads} "
                             f"key heads, conv_L {conv_L} (at least 2)")
        if num_layers < full_interval:
            raise ValueError("no full-attention layer: the slab's sequence "
                             "rung is a full layer's")
        self._init_drawn(vocab_size, max_len, norm_eps, param_dtype, init_std,
                         n_experts, experts_per_token, held)
        self.d_model, self.num_heads = int(d_model), int(num_heads)
        self.kv_heads, self.head_dim = int(kv_heads), int(head_dim)
        self.rotary_dim, self.rope_theta = int(rotary_dim), float(rope_theta)
        self.lin_k_heads, self.lin_v_heads = int(lin_k_heads), int(lin_v_heads)
        self.lin_k_dim, self.lin_v_dim = int(lin_k_dim), int(lin_v_dim)
        self.conv_L = int(conv_L)
        self.d_ff_expert, self.d_ff_shared = int(d_ff_expert), int(d_ff_shared)
        self.layer_types = tuple(
            FULL if (li + 1) % int(full_interval) == 0 else LINEAR
            for li in range(int(num_layers)))

    @property
    def conv_dim(self):
        """Channels of the convolution: q, k and v side by side."""
        return 2 * self.lin_k_heads * self.lin_k_dim \
            + self.lin_v_heads * self.lin_v_dim

    def _decay_log(self):
        """A_log [Hv]: at a = 0 (dt_bias drawn about 0) exp(g) runs from
        0.5 to 0.999 over the heads, geometric in -log exp(g), so that
        some heads forget within a few positions and some carry a state
        across the whole context (a fault in the state shows)."""
        lo = np.log(-np.log(0.999) / np.log(2.0))
        return np.linspace(0.0, lo, self.lin_v_heads).astype(np.float32)

    def _param_table(self):
        """Every parameter: (dotted name under the model, shape, dtype,
        std of its normal draw or None, constant value or None)."""
        d, H, G, D = self.d_model, self.num_heads, self.kv_heads, self.head_dim
        Hk, Hv = self.lin_k_heads, self.lin_v_heads
        Dk, Dv = self.lin_k_dim, self.lin_v_dim
        pd, f32, std = self.param_dtype, np.dtype("float32"), self.init_std
        E, f, fs = self.held[1], self.d_ff_expert, self.d_ff_shared
        # the norms' gains are 1 + g with g drawn, not zero: a dropped
        # `+ 1` shows; the gated norm's w is plain, its published 1
        out = [("embed.W", (self.vocab_size, d), pd, std, None)]
        for li, kind in enumerate(self.layer_types):
            pre = f"blocks.l{li}."
            out.append((pre + "ln1.g", (d,), f32, 0.1, None))
            if kind == LINEAR:
                out += [
                    (pre + "lin.W_qkvz", (d, 2 * Hk * Dk + 2 * Hv * Dv), pd,
                     std, None),
                    (pre + "lin.W_ba", (d, 2 * Hv), pd, std, None),
                    # std 0.3: taps of the size a trained filter has
                    (pre + "lin.conv", (self.conv_L, self.conv_dim), pd, 0.3,
                     None),
                    (pre + "lin.A_log", (Hv,), f32, None, self._decay_log()),
                    (pre + "lin.dt_bias", (Hv,), f32, 0.1, None),
                    (pre + "lin.norm", (Dv,), f32, None, 1.0),
                    (pre + "lin.W_out", (Hv * Dv, d), pd, std, None)]
            else:
                out += [
                    (pre + "attn.W_qkv", (d, 2 * H * D + 2 * G * D), pd, std,
                     None),
                    (pre + "attn.q_norm", (D,), f32, 0.1, None),
                    (pre + "attn.k_norm", (D,), f32, 0.1, None),
                    (pre + "attn.W_o", (H * D, d), pd, std, None)]
            out += [
                (pre + "ln2.g", (d,), f32, 0.1, None),
                (pre + "moe.W_r", (d, self.n_experts), f32, std, None),
                (pre + "moe.W_g", (E, d, f), pd, std, None),
                (pre + "moe.W_u", (E, d, f), pd, std, None),
                (pre + "moe.W_d", (E, f, d), pd, std, None),
                (pre + "shared.W_g", (d, fs), pd, std, None),
                (pre + "shared.W_u", (d, fs), pd, std, None),
                (pre + "shared.W_d", (fs, d), pd, std, None),
                (pre + "shared.gate", (d, 1), pd, std, None)]
        return out + [("ln_f.g", (d,), f32, 0.1, None),
                      ("head.W", (d, self.vocab_size), pd, std, None)]

    def _tree(self, leaf):
        """The tree every program receives, from `leaf(dotted name)`."""
        blocks = []
        for li, kind in enumerate(self.layer_types):
            pre = f"blocks.l{li}."
            part, names = (("lin.", ("W_qkvz", "W_ba", "conv", "A_log",
                                     "dt_bias", "norm", "W_out"))
                           if kind == LINEAR else
                           ("attn.", ("W_qkv", "q_norm", "k_norm", "W_o")))
            blocks.append({
                "ln1": leaf(pre + "ln1.g"), "ln2": leaf(pre + "ln2.g"),
                "mix": {n: leaf(pre + part + n) for n in names},
                "ffn": {n: leaf(pre + "moe." + n)
                        for n in ("W_r", "W_g", "W_u", "W_d")},
                "shared": {n: leaf(pre + "shared." + n)
                           for n in ("W_g", "W_u", "W_d", "gate")}})
        return {"embed": leaf("embed.W"), "blocks": blocks,
                "ln_f": leaf("ln_f.g"), "head": leaf("head.W")}

    # -- what is not implemented, by mechanism -----------------------------
    def _shard_decode_params(self, params, mesh):
        raise NotImplementedError(
            "GatedDeltaMoELM: the tensor-parallel shard path is not "
            "implemented: 2 key/value heads and the recurrent matrix "
            "states have no sharding rule, and experts across chips need "
            "their exchange")

    # -- the mathematics ---------------------------------------------------
    def _norm(self, h, g, dtype):
        """Zero-centred RMSNorm (gain 1 + g) in float32, in `dtype`."""
        import jax.numpy as jnp

        return self._rms(h.astype(jnp.float32), 1.0 + g).astype(dtype)

    def _gated_delta(self, p, x, convolve, delta):
        """A Gated DeltaNet layer's mixer over the normed x [B, S, d]:
        `convolve(u, w)` takes the convolution's inputs u [B, S, C]
        ([q | k | v] a key head) and the taps w [L, C] dealt to that
        order (float32) and returns its output before the silu
        in float32; `delta(q, k, v, g, beta)` takes q, k [B, S, Hv, Dk]
        (normed, q scaled), v [B, S, Hv, Dv], g and beta [B, S, Hv] and
        returns o [B, S, Hv, Dv] float32. Returns the mixer's output."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        prec = tensor.get_matmul_precision()
        B, S, _ = x.shape
        Hk, Hv = self.lin_k_heads, self.lin_v_heads
        Dk, Dv = self.lin_k_dim, self.lin_v_dim
        r = Hv // Hk
        # the projection by key-head group, and the convolution over
        # its channels as grouped: a plain product gathered into q, k
        # and v made XLA re-lay W_qkvz out at every use in a block
        qkvz = jnp.einsum("bsd,dgc->bsgc", x, p["W_qkvz"].reshape(
            x.shape[-1], Hk, 2 * Dk + 2 * r * Dv), precision=prec)
        qkv, z = jnp.split(qkvz, [2 * Dk + r * Dv], -1)
        ba = jnp.matmul(x, p["W_ba"], precision=prec).reshape(B, S, Hk, 2 * r)
        b = ba[..., :r].reshape(B, S, Hv).astype(jnp.float32)
        a = ba[..., r:].reshape(B, S, Hv).astype(jnp.float32)
        with jax.named_scope("gdn_conv"):
            # depthwise, so the taps (stored q, k, v each whole) are
            # dealt to the grouped order, [q | k | v] a key head
            L = p["conv"].shape[0]
            w = jnp.concatenate(
                [p["conv"][:, :Hk * Dk].reshape(L, Hk, Dk),
                 p["conv"][:, Hk * Dk:2 * Hk * Dk].reshape(L, Hk, Dk),
                 p["conv"][:, 2 * Hk * Dk:].reshape(L, Hk, r * Dv)], -1)
            c = jax.nn.silu(convolve(
                qkv.reshape(B, S, -1),
                w.reshape(L, -1).astype(jnp.float32))).reshape(
                    B, S, Hk, 2 * Dk + r * Dv)
            q, k, v = jnp.split(c, [Dk, 2 * Dk], -1)

            def l2(t):
                t = jnp.repeat(t, r, axis=2)
                return t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True)
                                     + 1e-6)

            q, k = l2(q) / float(np.sqrt(Dk)), l2(k)
            v = v.reshape(B, S, Hv, Dv)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
        with jax.named_scope("gdn_delta"):
            o = delta(q, k, v, g, beta)
        with jax.named_scope("gdn_norm"):
            o = self._rms(o, p["norm"]) * jax.nn.silu(
                z.reshape(B, S, Hv, Dv).astype(jnp.float32))
        return jnp.matmul(o.reshape(B, S, Hv * Dv).astype(x.dtype),
                          p["W_out"], precision=prec)

    def _attention(self, p, x, pos, attend):
        """A full layer's mixer over the normed x [B, S, d] at positions
        pos [B, S]; `attend(q, k, v)` as `ShortConvMoELM._stack`'s."""
        import jax
        import jax.numpy as jnp

        prec = tensor.get_matmul_precision()
        B, S, _ = x.shape
        H, G, D = self.num_heads, self.kv_heads, self.head_dim
        with jax.named_scope("attn_full"):
            qg, k, v = jnp.split(jnp.matmul(x, p["W_qkv"], precision=prec),
                                 [2 * H * D, (2 * H + G) * D], -1)
            q, gate = jnp.split(qg.reshape(B, S, H, 2 * D), 2, -1)
            q = rope(self._norm(q, p["q_norm"], x.dtype), pos,
                     self.rope_theta, self.rotary_dim)
            k = rope(self._norm(k.reshape(B, S, G, D), p["k_norm"], x.dtype),
                     pos, self.rope_theta, self.rotary_dim)
            o = attend(q.reshape(B, S, G, H // G, D), k, v.reshape(B, S, G, D))
            o = o.reshape(B, S, H, D).astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))
            return jnp.matmul(o.reshape(B, S, H * D).astype(x.dtype),
                              p["W_o"], precision=prec)

    def _stack(self, params, ids, pos, attend, convolve, delta):
        """Embedding through the final norm for ids [B, S] at positions
        pos [B, S]. The caller's callbacks keep what their cache keeps:
        `attend(li, q, k, v)`, `convolve(li, u, w)` and `delta(li, q, k,
        v, g, beta)` (`_attention`'s and `_gated_delta`'s, with the
        layer's index). Returns (hidden [B, S, d] float32, the routed
        layers' counters [3])."""
        import functools

        import jax
        import jax.numpy as jnp

        prec = tensor.get_matmul_precision()
        B, S = ids.shape
        dt = params["embed"].dtype
        h = params["embed"][ids].astype(jnp.float32)
        counters = jnp.zeros(3, jnp.int32)
        for li, (kind, blk) in enumerate(zip(self.layer_types,
                                             params["blocks"])):
            x = self._norm(h, blk["ln1"], dt)
            if kind == LINEAR:
                h = h + self._gated_delta(
                    blk["mix"], x, functools.partial(convolve, li),
                    functools.partial(delta, li))
            else:
                h = h + self._attention(blk["mix"], x, pos,
                                        functools.partial(attend, li))
            x = self._norm(h, blk["ln2"], dt)
            y, counts = self._experts(blk["ffn"], x.reshape(B * S, -1), prec)
            with jax.named_scope("shared_expert"):
                sh = blk["shared"]
                gate = jax.nn.sigmoid(jnp.matmul(
                    x, sh["gate"], precision=prec,
                    preferred_element_type=jnp.float32))
                y = y.reshape(B, S, -1) + (gate * jnp.matmul(
                    jax.nn.silu(jnp.matmul(x, sh["W_g"], precision=prec))
                    * jnp.matmul(x, sh["W_u"], precision=prec),
                    sh["W_d"], precision=prec)).astype(dt)
            h = h + y
            counters = counters + jnp.stack(
                [counts.sum(), (counts > 0).sum(), counts.max()])
        return h, counters

    def _head(self, params, h):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("head"):
            x = self._norm(h, params["ln_f"], params["head"].dtype)
            return jnp.matmul(x, params["head"],
                              precision=tensor.get_matmul_precision(),
                              preferred_element_type=jnp.float32)

    def _prompt_delta(self, n_real, states=None):
        """`delta` over whole prompts whose first n_real [B] positions
        are real: the chunked form from zero states, g = 0 and beta = 0
        past n_real. `states`, a list, gets each layer's state after
        the prompt [B, Hv, Dk, Dv] float32."""
        import jax.numpy as jnp

        def delta(li, q, k, v, g, beta):
            B, S, Hv = g.shape
            real = (jnp.arange(S)[None, :] < n_real[:, None])[..., None]
            o, state = chunk_gated_delta(
                *(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
                *(jnp.where(real, t, 0.0).transpose(0, 2, 1)
                  for t in (g, beta)),
                jnp.zeros((B, Hv, self.lin_k_dim, self.lin_v_dim),
                          jnp.float32), CHUNK)
            if states is not None:
                states.append(state)
            return o.transpose(0, 2, 1, 3)

        return delta

    # -- eval forward --------------------------------------------------------
    def _eval_logits(self, params, ids):
        import jax.numpy as jnp

        B, S = ids.shape
        prec = tensor.get_matmul_precision()
        h, _ = self._stack(
            params, ids, jnp.broadcast_to(jnp.arange(S), (B, S)),
            lambda li, q, k, v: attend_prompts(q, k, v, prec),
            lambda li, u, w: conv_prompt(u, w),
            self._prompt_delta(jnp.full((B,), S)))
        return self._head(params, h)

    # -- the slab: recurrent and convolution states beside contexts --------
    def new_slab(self, params, slots, seq, device):
        """Per layer {"S": [slots, Hv, Dk, Dv] float32, "u": [slots,
        L-1, C]} where it runs the delta rule, {"k", "v": [slots, Hkv,
        D, seq]} where it attends."""
        import jax.numpy as jnp

        dt = params["embed"].dtype
        G, D = self.kv_heads, self.head_dim
        return [{"S": jnp.zeros((slots, self.lin_v_heads, self.lin_k_dim,
                                 self.lin_v_dim), jnp.float32, device=device),
                 "u": jnp.zeros((slots, self.conv_L - 1, self.conv_dim), dt,
                                device=device)}
                if kind == LINEAR else
                {n: jnp.zeros((slots, G, D, seq), dt, device=device)
                 for n in ("k", "v")}
                for kind in self.layer_types]

    def grow_slab(self, slab, new_seq):
        """Only what holds the context grows; a state is left alone."""
        import jax.numpy as jnp

        def grown(c):
            more = new_seq - c["v"].shape[3]
            return {n: jnp.pad(a, ((0, 0),) * 3 + ((0, more),))
                    for n, a in c.items()}

        return [c if kind == LINEAR else grown(c)
                for kind, c in zip(self.layer_types, slab)]

    def slab_dims(self, slab):
        v = slab[self.layer_types.index(FULL)]["v"]
        return int(v.shape[0]), int(v.shape[3])

    def slab_bytes(self, slab):
        kinds = {"S": "recurrent", "u": "state", "k": "context",
                 "v": "context"}
        out = dict.fromkeys(("recurrent", "state", "context"), 0)
        for c in slab:
            for n, a in c.items():
                out[kinds[n]] += a.size * a.dtype.itemsize
        return out

    # -- the programs' step functions --------------------------------------
    def _slot_step(self, params, slab, tok, pos):
        """One fused decode step over every slot at per-row positions: a
        full layer writes row b's key and value at pos[b], in place
        (`cache_write`), and attends positions 0..pos[b]; a Gated
        DeltaNet layer convolves from the row's state and puts its
        input over the oldest column, and advances the row's matrix
        states by one position (`gated_delta_step`, in place). Returns
        (logits [B, V], new slab, counters [4]: the routed layers'
        three, then the rows whose states it advanced)."""
        import jax.numpy as jnp

        from ..ops.pallas_kernels import cache_write, gated_delta_step

        prec = tensor.get_matmul_precision()
        B = tok.shape[0]
        new = [dict() for _ in slab]
        tap = conv_columns(pos, self.conv_L)

        def attend(li, q, k, v):
            c = slab[li]
            new[li]["k"] = cache_write(c["k"], k[:, 0], pos, axis=3)
            new[li]["v"] = cache_write(c["v"], v[:, 0], pos, axis=3)
            return attend_cache(q[:, 0], new[li]["k"], new[li]["v"], pos,
                                prec, values="bhdt")[:, None]

        def convolve(li, u, w):
            z, new[li]["u"] = conv_step(slab[li]["u"], u, w, tap)
            return z

        def delta(li, q, k, v, g, beta):
            o, new[li]["S"] = gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], slab[li]["S"])
            return o[:, None]

        h, counters = self._stack(params, tok[:, None], pos[:, None], attend,
                                  convolve, delta)
        rows = B * self.layer_types.count(LINEAR)
        counters = jnp.concatenate([counters, jnp.full((1,), rows,
                                                       jnp.int32)])
        # each layer's entries in the slab's own order
        new = [{n: d[n] for n in c} for c, d in zip(slab, new)]
        return self._head(params, h[:, 0]), new, counters

    def _prefill_rows(self, params, slab, ids, n_real, slots):
        """A cohort of bucket-padded prompts [Bp, Pb] through the
        stack, their state written into slab rows `slots` (a row whose
        slot is out of bounds writes nothing). A context takes
        positions 0..Pb-1 (the pad tail is hidden by the causal mask
        and overwritten before any query attends it); a convolution
        state takes the inputs at the row's last L-1 REAL positions
        (zero where the prompt is shorter); a recurrent state is the
        one after the row's last real position, whatever the bucket's
        length."""
        import jax.numpy as jnp

        prec = tensor.get_matmul_precision()
        Bp, Pb = ids.shape
        back = conv_tail_positions(n_real, self.conv_L)
        new = [dict() for _ in slab]
        states = []
        prompt_delta = self._prompt_delta(n_real, states)

        def attend(li, q, k, v):
            for n, t in (("k", k), ("v", v)):
                new[li][n] = put_rows(slab[li][n], t.transpose(0, 2, 3, 1),
                                      slots)
            return attend_prompts(q, k, v, prec)

        def convolve(li, u, w):
            new[li]["u"] = put_rows(slab[li]["u"], conv_tail(u, back), slots)
            return conv_prompt(u, w)

        def delta(li, *args):
            o = prompt_delta(li, *args)
            new[li]["S"] = put_rows(slab[li]["S"], states.pop().astype(
                slab[li]["S"].dtype), slots)
            return o

        pos = jnp.broadcast_to(jnp.arange(Pb), (Bp, Pb))
        h, _ = self._stack(params, ids, pos, attend, convolve, delta)
        last_h = jnp.take_along_axis(
            h, (n_real - 1)[:, None, None], axis=1)[:, 0]
        new = [{n: d[n] for n in c} for c, d in zip(slab, new)]
        return self._head(params, last_h), new


def create_model(vocab_size=256, **kwargs):
    return GatedDeltaMoELM(vocab_size, **kwargs)
