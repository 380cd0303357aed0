"""The Gated DeltaNet mixture-of-experts LM against its plain reference,
at toy widths with the served configuration's mechanism kept: d 64,
three Gated DeltaNet layers (2 key heads and 4 value heads, of 8 and
16; a 4-tap convolution) to one gated full-attention layer (4 query
heads over 2 key/value heads of 16, rotary on 4), a softmax router over
16 experts of which 4 are held, top-4, and a gated shared expert;
vocabulary 64; the prompt's chunks of 64 as published. The decays are
drawn as the cell's (exp(g) over 0.5 - 0.999 across the heads), so a
state carries across the whole context. Seeded float32 weights on the
CPU at "highest": 1e-4 on logits whose scale is about 3 is float32
rounding through four layers and a recurrence of 200 positions (what is
read is 2e-5), where a state kept from another session, one moved by a
padded position, a head read from the wrong group or a bfloat16 state
moves them by 1e-2 and more.
"""
import hashlib

import numpy as np
import pytest

from perfbench.reference import qwen3_next_ref as ref
from singa_tpu import device, serve, stats, tensor
from singa_tpu.models.gated_delta_moe import (GatedDeltaMoELM,
                                              chunk_gated_delta,
                                              unit_lower_inverse)

V = 64
KW = dict(d_model=64, num_layers=4, full_interval=4, num_heads=4, kv_heads=2,
          head_dim=16, rotary_dim=4, rope_theta=1e7, lin_k_heads=2,
          lin_v_heads=4, lin_k_dim=8, lin_v_dim=16, conv_L=4,
          d_ff_expert=16, d_ff_shared=16, n_experts=16, experts_per_token=4,
          held=(4, 4), norm_eps=1e-6, max_len=512, init_std=0.1)
ARCH = {k: KW[k] for k in (
    "num_layers", "full_interval", "num_heads", "kv_heads", "head_dim",
    "rotary_dim", "rope_theta", "lin_k_heads", "lin_v_heads", "lin_k_dim",
    "lin_v_dim", "conv_L", "experts_per_token", "held", "norm_eps")}
TOL = dict(rtol=0, atol=1e-4)
# two sequences of 200 ids: the reference reads each once, whole, and a
# test that wants fewer positions reads a prefix (it is causal); every
# prefill is a cohort of two rows in the 256 bucket, so that the toy's
# programs compile once for the file
BASE = np.random.default_rng(0).integers(0, V, (2, 200), dtype=np.int32)
BUCKET = 256


@pytest.fixture(autouse=True)
def _highest():
    before = tensor.get_matmul_precision()
    tensor.set_matmul_precision("highest")
    yield
    tensor.set_matmul_precision(before)


def build(seed=3, **over):
    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    m = GatedDeltaMoELM(V, **{**KW, **over})
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32), device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return build()


def states_of(m):
    return {k: v.data for k, v in m.get_states().items()}


def ref_logits(m, ids):
    return np.asarray(ref.logits(states_of(m), np.asarray(ids), **ARCH))


def ids_of(shape, seed=0):
    return np.random.default_rng(seed).integers(0, V, shape, dtype=np.int32)


def put(a):
    import jax.numpy as jnp

    return jnp.asarray(a)


def fresh_slab(m, slots=2, seq=256):
    import jax

    return m.new_slab(m._decode_params(), slots, seq, jax.devices()[0])


def prefill(m, slab, rows, bucket, slots=None):
    ids = np.zeros((len(rows), bucket), np.int32)
    for r, row in enumerate(rows):
        ids[r, :len(row)] = row
    n = np.asarray([len(r) for r in rows], np.int32)
    slots = np.arange(len(rows), dtype=np.int32) if slots is None else slots
    lg, slab = m.prefill_slab(m._decode_params(), slab, put(ids), put(n),
                              put(np.asarray(slots, np.int32)))
    return np.asarray(lg), slab


def step(m, slab, tok, pos):
    out, slab = m.decode_step(m._decode_params(), slab,
                              put(np.asarray(tok, np.int32)),
                              put(np.asarray(pos, np.int32)))
    return np.asarray(out), slab


def recur(S, x):
    """One position of the gated delta rule, every row and head: S [...,
    Dk, Dv], x = (q, k [..., Dk], v [..., Dv], g, beta [...]). Returns
    (the state after, (o, the state after))."""
    import jax.numpy as jnp

    qt, kt, vt, gt, bt = x
    S = S * jnp.exp(gt)[..., None, None]
    kv = jnp.einsum("...kv,...k->...v", S, kt)
    S = S + kt[..., None] * (bt[..., None] * (vt - kv))[..., None, :]
    return S, (jnp.einsum("...kv,...k->...v", S, qt), S)


@pytest.fixture(scope="module")
def base_logits(model):
    """The reference's logits along both BASE sequences [2, 200, V]."""
    return ref_logits(model, BASE)


@pytest.fixture(scope="module")
def first_layer_states(model):
    """The reference's state of the first layer (a Gated DeltaNet one)
    after each position of BASE[0] [200, Hv, Dk, Dv]: the inputs its
    `delta_rule` gets, run through the per-position recurrence."""
    import jax
    import jax.numpy as jnp

    st = states_of(model)
    seen = []
    delta_rule = ref.delta_rule

    def spy(*args):
        seen.append(args)
        return delta_rule(*args)

    pre = f"{ref.PREFIX}.blocks.l0"
    ref.delta_rule = spy
    try:
        ref.linear_layer(st[f"{ref.PREFIX}.embed.W"][jnp.asarray(BASE[0])],
                         tuple(st[f"{pre}.{n}"] for n in (
                             "ln1.g", "lin.W_qkvz", "lin.W_ba", "lin.conv",
                             "lin.A_log", "lin.dt_bias", "lin.norm",
                             "lin.W_out")), ref._arch(ARCH))
    finally:
        ref.delta_rule = delta_rule
    (q, k, v, g, beta), = seen
    with jax.default_matmul_precision("highest"):
        _, (_, every) = jax.lax.scan(recur, jnp.zeros(
            (q.shape[1], q.shape[2], v.shape[2]), jnp.float32),
            (q, k, v, g, beta))
    return np.asarray(every)


# -- (a) the eval forward -----------------------------------------------------
@pytest.mark.parametrize("S", [1, 130])
def test_a_eval_forward_equals_reference(model, base_logits, S):
    got = np.asarray(model.forward(tensor.from_numpy(BASE[:, :S])).data)
    np.testing.assert_allclose(got, base_logits[:, :S], **TOL)


def test_a_the_decays_carry_a_state(model):
    """exp(g) at a = 0 runs from 0.5 to 0.999 over the value heads, as
    the configuration's `assumed.decay_draw` states."""
    p = model._decode_params()["blocks"][0]["mix"]
    at_zero = np.exp(-np.exp(np.asarray(p["A_log"])) * np.log(2.0))
    np.testing.assert_allclose(at_zero[[0, -1]], [0.5, 0.999], rtol=1e-5)
    assert np.all(np.diff(at_zero) > 0)


# -- (b) the chunked form -----------------------------------------------------
def test_b_the_unit_lower_inverse_is_the_inverse():
    rng = np.random.default_rng(0)
    a = np.tril(rng.normal(size=(3, 64, 64)), -1).astype(np.float32) * 0.2
    import jax

    got = np.asarray(jax.jit(unit_lower_inverse)(put(a)))
    want = np.linalg.inv(np.eye(64) + a.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


CHUNK_LENGTHS = [1, 63, 64, 65, 200]


@pytest.fixture(scope="module")
def chunked_against_recurrence():
    """For each L of CHUNK_LENGTHS, rows of 3 heads whose first L
    positions of 256 are real, the rest g = 0 and beta = 0, from a
    state that is not zero: `chunk_gated_delta` over all of them in ONE
    program, and the per-position recurrence over each row's L alone
    (float32, "highest"). {L: ((o, S) chunked, (o, S) recurred)}."""
    import jax

    rng = np.random.default_rng(1)
    H, Dk, Dv, P = 3, 8, 16, 256
    B = 2 * len(CHUNK_LENGTHS)

    def l2(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = l2(rng.normal(size=(B, H, P, Dk))) / np.sqrt(Dk)
    k = l2(rng.normal(size=(B, H, P, Dk)))
    v = rng.normal(size=(B, H, P, Dv))
    g = -rng.uniform(0.0, 0.7, (B, H, P))
    beta = rng.uniform(0.0, 1.0, (B, H, P))
    S0 = rng.normal(size=(B, H, Dk, Dv)) * 0.3
    n = np.repeat(CHUNK_LENGTHS, 2)
    real = (np.arange(P)[None, :] < n[:, None])[:, None, :]
    o, S = map(np.asarray, jax.jit(chunk_gated_delta, static_argnums=6)(
        *(put(t.astype(np.float32)) for t in (
            q, k, v, np.where(real, g, 0), np.where(real, beta, 0), S0)),
        64))
    out = {}
    for i, L in enumerate(CHUNK_LENGTHS):
        rows = slice(2 * i, 2 * i + 2)
        with jax.default_matmul_precision("highest"):
            want_S, (want_o, _) = jax.lax.scan(
                recur, put(S0[rows].astype(np.float32)), tuple(
                    put(np.moveaxis(t[rows, :, :L], 2, 0).astype(np.float32))
                    for t in (q, k, v, g, beta)))
        out[L] = ((o[rows, :, :L], S[rows]),
                  (np.moveaxis(np.asarray(want_o), 0, 2), np.asarray(want_S)))
    return out


@pytest.mark.parametrize("L", CHUNK_LENGTHS)
def test_b_the_chunks_are_the_recurrence_and_padding_moves_nothing(
        chunked_against_recurrence, L):
    """`chunk_gated_delta` over L real positions padded to 256 with g =
    0 and beta = 0: every real position's output and the state after
    the last are the per-position recurrence's over the L alone, from a
    state that is not zero."""
    (o, S), (want_o, want_S) = chunked_against_recurrence[L]
    np.testing.assert_allclose(o, want_o, rtol=0, atol=1e-4)
    np.testing.assert_allclose(S, want_S, rtol=0, atol=1e-4)


@pytest.mark.parametrize("L", CHUNK_LENGTHS)
def test_b_a_prefill_in_a_larger_bucket_writes_the_state_after_its_prompt(
        model, base_logits, first_layer_states, L):
    """A prompt of L positions in a bucket of 256: its logits are the
    reference's at position L-1, and the first layer's matrix states in
    the slab are the reference recurrence's after position L-1, not
    after the pad tail."""
    lg, slab = prefill(model, fresh_slab(model),
                       [BASE[0, :L], ids_of((3,), 1)], BUCKET)
    np.testing.assert_allclose(lg[0], base_logits[0, L - 1], **TOL)
    np.testing.assert_allclose(np.asarray(slab[0]["S"][0]),
                               first_layer_states[L - 1], rtol=0, atol=1e-4)
    # the convolution state: the last three inputs, zero where the
    # prompt has no such position (column p mod 3 holds position p)
    u = np.asarray(slab[0]["u"][0])
    for p in range(L - 3, L):
        assert u[p % 3].any() == (p >= 0)


# -- (c) prefill, then decode through the slab --------------------------------
@pytest.mark.parametrize("P,total", [(3, 40), (70, 110), (130, 200)],
                         ids=["short", "two_chunks", "padded_bucket"])
def test_c_prefill_then_steps_equal_reference(model, base_logits, P, total):
    m = model
    full, want = BASE[1, :total], base_logits[1]
    other = ids_of((9,), seed=99)
    lg, slab = prefill(m, fresh_slab(m), [full[:P], other], BUCKET)
    np.testing.assert_allclose(lg[0], want[P - 1], **TOL)
    for t in range(P, total):
        lg, slab = step(m, slab, [full[t], other[-1]], [t, 9])
        np.testing.assert_allclose(lg[0], want[t], **TOL)


def test_c_a_reused_slot_keeps_nothing_of_its_last_session(model):
    """Slot 1 serves one session for 20 steps, then a new prompt is
    prefilled into it: from there on its logits are those of the same
    prompt in a slab that never held anything."""
    m = model
    first, second = ids_of((50,), 5), ids_of((30,), 6)
    _, slab = prefill(m, fresh_slab(m), [ids_of((4,), 7), first], BUCKET)
    tok = [1, 2]
    for t in range(20):
        lg, slab = step(m, slab, tok, [4 + t, 50 + t])
        tok = lg.argmax(-1)
    # the cohort's second row pads it: its slot, 2, is out of bounds and
    # writes nothing
    lg_reused, slab = prefill(m, slab, [second, second], BUCKET, slots=[1, 2])
    lg_fresh, clean = prefill(m, fresh_slab(m), [second, second], BUCKET,
                              slots=[1, 2])
    np.testing.assert_allclose(lg_reused[0], lg_fresh[0], rtol=0, atol=0)
    for t in range(5):
        a, slab = step(m, slab, [0, 3 + t], [24 + t, 30 + t])
        b, clean = step(m, clean, [0, 3 + t], [24 + t, 30 + t])
        np.testing.assert_allclose(a[1], b[1], rtol=0, atol=1e-6)


def test_c_the_step_counts_the_rows_it_advanced(model):
    """Every slot's states (a step cannot tell a free one) times the
    three Gated DeltaNet layers; the routed layers' three counters
    beside."""
    m = model
    _, slab = prefill(m, fresh_slab(m), [ids_of((5,), 2), ids_of((3,), 1)],
                      BUCKET)
    step(m, slab, [1, 2], [5, 3])
    got = m.take_step_counters()
    assert got["gdn_state_rows"] == 2 * 3
    assert 0 < got["moe_assignments_local"] <= 2 * 4 * 4


# -- (d) the decode kernel ----------------------------------------------------
@pytest.mark.parametrize("shape,dtype", [
    ((3, 4, 8, 16), "float32"), ((2, 32, 16, 8), "float32"),
    ((2, 4, 8, 16), "bfloat16")], ids=["toy", "two_blocks", "bf16_state"])
def test_d_gated_delta_step_is_one_position_of_the_recurrence(shape, dtype):
    """Interpreted, against the recurrence in numpy; a state held in
    bfloat16 is read and written in it."""
    import jax.numpy as jnp

    from singa_tpu.ops.pallas_kernels import gated_delta_step

    B, H, Dk, Dv = shape
    rng = np.random.default_rng(sum(shape))

    def l2(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = (l2(rng.normal(size=(B, H, Dk))) / np.sqrt(Dk)).astype(np.float32)
    k = l2(rng.normal(size=(B, H, Dk))).astype(np.float32)
    v = rng.normal(size=(B, H, Dv)).astype(np.float32)
    g = -rng.uniform(0, 1, (B, H)).astype(np.float32)
    beta = rng.uniform(0, 1, (B, H)).astype(np.float32)
    S = jnp.asarray(rng.normal(size=(B, H, Dk, Dv)), dtype)
    s = np.asarray(S.astype(jnp.float32)) * np.exp(g)[..., None, None]
    d = beta[..., None] * (v - np.einsum("bhkv,bhk->bhv", s, k))
    s = s + k[..., :, None] * d[..., None, :]
    o, new = gated_delta_step(*(put(t) for t in (q, k, v, g, beta)), S)
    assert new.dtype == jnp.dtype(dtype) and o.dtype == jnp.float32
    tol = 1e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(new.astype(jnp.float32)), s,
                               rtol=0, atol=tol)
    np.testing.assert_allclose(np.asarray(o),
                               np.einsum("bhkv,bhk->bhv", s, q), rtol=0,
                               atol=1e-5 if dtype == "float32" else 2e-2)


# -- (e) the layouts the published checkpoint fixes ---------------------------
def test_e_qkvz_and_ba_are_grouped_by_key_head(model):
    """The mixer's inputs, against `fix_query_key_value_ordering` written
    out in numpy: x W_qkvz is 2 groups of [q 8 | k 8 | v 2x16 | z 2x16],
    x W_ba 2 groups of [b 2 | a 2]; v, z, b and a are read as 4 value
    heads, and value head h uses key head h // 2 (repeat_interleave).
    With the taps the identity (the convolution's output is its input),
    what the delta rule gets is silu of those, q and k l2-normed."""
    import jax.numpy as jnp

    m = model
    p = m._decode_params()["blocks"][0]["mix"]
    x = put(np.random.default_rng(1).normal(size=(1, 3, 64)).astype(
        np.float32))
    seen = {}

    def convolve(u, w):
        seen["u"] = np.asarray(u)
        return jnp.zeros(u.shape, jnp.float32) + u

    def delta(q, k, v, g, beta):
        seen.update(q=np.asarray(q), k=np.asarray(k), v=np.asarray(v),
                    g=np.asarray(g), beta=np.asarray(beta))
        return jnp.zeros(v.shape, jnp.float32)

    m._gated_delta(p, x, convolve, delta)
    Hk, Hv, Dk, Dv, r = 2, 4, 8, 16, 2
    qkvz = np.asarray(x)[0] @ np.asarray(p["W_qkvz"])
    mixed = qkvz.reshape(3, Hk, 2 * Dk + 2 * r * Dv)
    q, k, v, z = np.split(mixed, np.cumsum([Dk, Dk, r * Dv]), -1)
    v = v.reshape(3, Hv, Dv)
    ba = (np.asarray(x)[0] @ np.asarray(p["W_ba"])).reshape(3, Hk, 2 * r)
    b, a = ba[..., :r].reshape(3, Hv), ba[..., r:].reshape(3, Hv)
    # the depthwise convolution runs over the channels as grouped,
    # [q | k | v] a key head
    np.testing.assert_allclose(seen["u"][0], np.concatenate(
        [q, k, v.reshape(3, Hk, r * Dv)], -1).reshape(3, -1),
        rtol=1e-5, atol=1e-5)
    def silu(t):
        return t / (1 + np.exp(-t))

    def l2(t):
        return t / np.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    for h in range(Hv):
        np.testing.assert_allclose(seen["k"][0, :, h], l2(silu(k[:, h // r])),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(seen["q"][0, :, h],
                                   l2(silu(q[:, h // r])) / np.sqrt(Dk),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(seen["v"][0, :, h], silu(v[:, h]),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(seen["beta"][0], 1 / (1 + np.exp(-b)),
                               rtol=1e-5, atol=1e-6)
    softplus = np.log1p(np.exp(a + np.asarray(p["dt_bias"])))
    np.testing.assert_allclose(
        seen["g"][0], -np.exp(np.asarray(p["A_log"])) * softplus,
        rtol=1e-5, atol=1e-6)
    assert z.shape == (3, Hk, r * Dv)


# -- (f) the routed layer -----------------------------------------------------
def test_f_softmax_top_k_is_renormalised_over_the_chosen():
    """With every expert held, each row's weights are its top-k softmax
    probabilities over the sum of those k: a row of products equal to
    one expert's output times that weight, the weights summing to 1."""
    import jax

    from singa_tpu.models.routed_experts import routed_experts

    d, f, E, K = 8, 4, 6, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    ffn = {"W_r": jax.random.normal(ks[0], (d, E)),
           "W_g": jax.random.normal(ks[1], (E, d, f)) * 0.5,
           "W_u": jax.random.normal(ks[2], (E, d, f)) * 0.5,
           "W_d": jax.random.normal(ks[3], (E, f, d)) * 0.5}
    x = jax.random.normal(jax.random.PRNGKey(1), (5, d))
    y, counts = jax.jit(lambda ffn, x: routed_experts(
        ffn, x, "highest", held=(0, E), experts_per_token=K,
        scoring="softmax"))(ffn, x)
    xn = np.asarray(x, np.float64)
    logits = xn @ np.asarray(ffn["W_r"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.zeros_like(xn)
    for n in range(5):
        top = np.argsort(-p[n])[:K]
        w = p[n, top] / p[n, top].sum()
        assert abs(w.sum() - 1.0) < 1e-12
        for e, we in zip(top, w):
            gt = xn[n] @ np.asarray(ffn["W_g"][e])
            ut = xn[n] @ np.asarray(ffn["W_u"][e])
            want[n] += we * ((gt / (1 + np.exp(-gt))) * ut) @ np.asarray(
                ffn["W_d"][e])
    np.testing.assert_allclose(np.asarray(y), want, rtol=0, atol=1e-4)
    assert int(counts.sum()) == 5 * K
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        routed_experts(ffn, x, "highest", held=(0, E), experts_per_token=K,
                       scoring="relu")


@pytest.mark.parametrize("shares", [[(0, 8), (8, 8)],
                                    [(0, 6), (6, 5), (11, 5)],
                                    [(0, 4), (4, 4), (8, 4), (12, 4)]],
                         ids=["two", "three_uneven", "four"])
def test_f_the_held_shares_and_the_shared_expert_make_the_whole_layer(
        shares):
    """The deployment the cut stands for: the routed parts of 2, 3 or 4
    shares of 16 experts, plus the gated shared expert once, are the
    reference's layer with every expert held."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.models.routed_experts import routed_experts

    d, f, E, K = 32, 16, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(len(shares)), 8)
    ffn = {"W_r": jax.random.normal(keys[0], (d, E)) * 0.3,
           "W_g": jax.random.normal(keys[2], (E, d, f)) * 0.3,
           "W_u": jax.random.normal(keys[3], (E, d, f)) * 0.3,
           "W_d": jax.random.normal(keys[4], (E, f, d)) * 0.3}
    shared = [jax.random.normal(k, s) * 0.3 for k, s in zip(
        keys[5:], ((d, f), (d, f), (f, d)))]
    gate_w = jax.random.normal(keys[1], (d, 1)) * 0.3
    h = jax.random.normal(jax.random.PRNGKey(2), (40, d))
    ln = jnp.zeros(d)
    @jax.jit
    def mine(h):
        n = ref._rms(h, ln, 1e-6)
        parts = sum(routed_experts(
            {**ffn, **{w: ffn[w][a:a + c] for w in ("W_g", "W_u", "W_d")}},
            n, "highest", held=(a, c), experts_per_token=K,
            scoring="softmax")[0] for a, c in shares)
        with jax.default_matmul_precision("highest"):
            return parts + jax.nn.sigmoid(n @ gate_w) * (
                jax.nn.silu(n @ shared[0]) * (n @ shared[1])) @ shared[2]

    @jax.jit
    def whole(h):
        arch = ref._arch({"norm_eps": 1e-6, "experts_per_token": K,
                          "held": (0, E)})
        wts = ref.router_weights(h, (ln, ffn["W_r"]), arch)
        y = ref.expert_layer(jnp.zeros_like(h), h,
                             ref.shared_gate(h, (ln, gate_w), arch),
                             (ln, *shared), arch)
        for e in range(E):
            y = ref.expert_layer(y, h, wts[:, e], (
                ln, ffn["W_g"][e], ffn["W_u"][e], ffn["W_d"][e]), arch)
        return y

    np.testing.assert_allclose(np.asarray(mine(h)), np.asarray(whole(h)),
                               rtol=0, atol=2e-5)


def _sigmoid_only_routed_experts(ffn, x, prec, *, held, experts_per_token,
                         dense_rows=256, sum_eps=0.0, act=None, scale=1.0):
    """`routed_experts` as it was before a second scoring rule, word for
    word: its sigmoid router alone."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    first, E = held
    K = experts_per_token
    N = x.shape[0]
    all_held = first == 0 and E == ffn["W_r"].shape[-1]
    with jax.named_scope("moe_router"):
        sig = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), ffn["W_r"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, idx = lax.top_k(sig + ffn["b"], K)              # [N,K]
        chosen = jnp.take_along_axis(sig, idx, -1)
        total = jnp.sum(chosen, -1, keepdims=True)
        if sum_eps:
            total = total + sum_eps
        w = chosen / total
        local = idx - first
        here = (local >= 0) & (local < E)
        local = jnp.where(here, local, E)                 # E: elsewhere
        counts = jnp.zeros(E + 1, jnp.int32).at[
            local.reshape(-1)].add(1)[:E]
    with jax.named_scope("moe_experts"):
        if N <= dense_rows:
            cw = jnp.sum(jax.nn.one_hot(local, E + 1, dtype=jnp.float32)
                         [..., :E] * w[..., None], 1)      # [N,E]
            g = jnp.einsum("nd,edf->enf", x, ffn["W_g"], precision=prec)
            u = jnp.einsum("nd,edf->enf", x, ffn["W_u"], precision=prec)
            a = (jax.nn.silu(g) * u if act is None else act(g, u)) \
                * cw.T[:, :, None].astype(x.dtype)
            y = jnp.einsum("enf,efd->nd", a, ffn["W_d"], precision=prec)
            return (y if scale == 1.0 else y * scale), counts
        flat = local.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        ws = jnp.where(here, w, 0.0).reshape(-1)
        at = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * K, dtype=order.dtype)).reshape(N, K)
        rd = functools.partial(lax.ragged_dot, group_sizes=counts,
                               precision=prec)

        def through(rows):
            o = order[:rows]
            xs = x[o // K]
            if act is None:
                a = jax.nn.silu(rd(xs, ffn["W_g"])) * rd(xs, ffn["W_u"])
            else:
                a = act(rd(xs, ffn["W_g"]), rd(xs, ffn["W_u"]))
            y = rd(a, ffn["W_d"])                          # [rows,d]
            y = jnp.where((flat[o] < E)[:, None],
                          y * ws[o][:, None].astype(y.dtype), 0)
            y = jnp.where((at < rows)[..., None],
                          y[jnp.minimum(at, rows - 1)], 0)  # [N,K,d]
            return y.astype(jnp.float32).sum(1).astype(x.dtype)

        if all_held:
            y = through(N * K)
            return (y if scale == 1.0 else y * scale), counts
        few = N * K // 4
        y = lax.cond(counts.sum() <= few, lambda: through(few),
                     lambda: through(N * K))
        return (y if scale == 1.0 else y * scale), counts


def _sigmoid_routed(name):
    """A toy of each model that routes by sigmoid scores."""
    from singa_tpu.models.block_sparse_moe import BlockSparseMoELM
    from singa_tpu.models.hybrid_moe import HybridWindowMoELM
    from singa_tpu.models.shortconv_moe import ShortConvMoELM

    cls, kw = {
        "hybrid": (HybridWindowMoELM, dict(
            d_model=32, num_heads=4, head_dim=12, v_head_dim=8,
            kv_heads_full=1, kv_heads_window=2, window=4, rotary_dim=4,
            layer_pattern=(0, 1, 0), moe_layers=(0, 1, 1), d_ff=64,
            d_ff_expert=16, n_experts=8, experts_per_token=2, held=(2, 4))),
        "shortconv": (ShortConvMoELM, dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8,
            layer_types=("conv", "full_attention", "conv"),
            num_dense_layers=1, d_ff=48, d_ff_expert=16, n_experts=8,
            experts_per_token=2, held=(0, 8))),
        "blocksparse": (BlockSparseMoELM, dict(
            d_model=48, num_heads=4, kv_heads=2, head_dim=12, rotary_dim=4,
            index_heads=2, index_dim=12, block=4, top_blocks=1,
            local_blocks=1, moe_layers=(0, 1), d_ff=64, d_ff_expert=16,
            d_ff_shared=16, n_experts=8, experts_per_token=2, held=(2, 4),
            prefill_block=8, prefill_tile=4))}[name]
    return cls(V, max_len=64, init_std=0.3, **kw)


@pytest.mark.parametrize("name", ["hybrid", "shortconv", "blocksparse"])
def test_f_the_sigmoid_models_lower_to_the_programs_they_had(name,
                                                             monkeypatch):
    """A toy decode step and cohort prefill of each sigmoid-routed
    model lower text for text as with the routed layer before the
    softmax rule: adding it changed none of their programs. Nothing is
    drawn: the programs are lowered for the parameters' and the slab's
    shapes."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.models import drawn_lm

    m = _sigmoid_routed(name)
    table = {n: jax.ShapeDtypeStruct(tuple(shape), dtype)
             for n, shape, dtype, _, _ in m._param_table()}
    p = m._tree(table.__getitem__)
    slab = jax.eval_shape(lambda: m.new_slab(p, 4, 16, None))
    args = {"step": (p, slab, jax.ShapeDtypeStruct((4,), jnp.int32),
                     jax.ShapeDtypeStruct((4,), jnp.int32)),
            "prefill": (p, slab, jax.ShapeDtypeStruct((2, 16), jnp.int32),
                        jax.ShapeDtypeStruct((2,), jnp.int32),
                        jax.ShapeDtypeStruct((2,), jnp.int32))}

    def texts():
        return [hashlib.sha1(jax.jit(fn).lower(*args[kind]).as_text()
                             .encode()).hexdigest()
                for kind, fn in (("step", m._slot_step),
                                 ("prefill", m._prefill_rows))]

    now = texts()
    monkeypatch.setattr(drawn_lm, "routed_experts",
                        lambda *a, scoring, **kw: _sigmoid_only_routed_experts(
                            *a, **kw))
    assert now == texts()


# -- (g) through the engine ---------------------------------------------------
def test_g_through_the_engine_sessions_stream_what_the_reference_picks(
        model):
    """Through `ServingEngine` (`warm_decode`, `submit_decode`, the token
    program, run-ahead blocks chained behind one another): each reply
    is what the reference picks; the gauges name the slab's three kinds
    and the step counts its rows."""
    m = model
    requests = [(BASE[0, :70], 40), (BASE[1, :100], 30)]
    stats.reset_cache_stats()
    eng = serve.ServingEngine(m, max_sessions=2, max_new_tokens=64,
                              prefill_batch=2, decode_block=2).start()
    try:
        eng.warm_decode(prompt_lens=(128,), max_new_tokens=64)
        replies = [eng.submit_decode(p, n) for p, n in requests]
        got = [np.asarray(r.result(timeout=300))[0] for r in replies]
    finally:
        eng.stop()
    d = stats.cache_stats()["decode"]
    assert d["host_leaves_per_call"] == 0
    assert d["cache_bytes_ring"] == 0
    assert d["cache_bytes_recurrent"] == 3 * 2 * 4 * 8 * 16 * 4
    assert d["cache_bytes_state"] == 3 * 2 * 3 * (2 * 2 * 8 + 4 * 16) * 4
    rung = d["cache_bytes_context"] // (2 * 2 * 2 * 16 * 4)
    assert rung >= 128 and rung & (rung - 1) == 0
    assert d["gdn_state_rows"] == 3 * 2 * d["decode_steps"] > 0
    for (prompt, n), full in zip(requests, got):
        assert len(full) == len(prompt) + n
        # padded to BASE's length, so the reference's programs are the
        # ones it already has; causal, so the padding changes nothing
        want = ref_logits(m, np.pad(full, (0, 200 - len(full)))[None])[0]
        at = np.arange(len(prompt) - 1, len(full) - 1)
        assert (want[at].max(-1) - want[at, full[at + 1]]).max() < 1e-4


def test_g_growth_pads_only_the_contexts(model):
    m = model
    _, slab = prefill(m, fresh_slab(m), [ids_of((20,), 4), ids_of((3,), 1)],
                      BUCKET)
    grown = m.grow_slab(slab, 512)
    assert m.slab_dims(grown) == (2, 512)
    for kind, a, b in zip(m.layer_types, slab, grown):
        for n in a:
            if kind == "full_attention":
                assert b[n].shape[3] == 512
                assert np.array_equal(np.asarray(b[n])[..., :256],
                                      np.asarray(a[n]))
            else:
                assert b[n] is a[n]
    by_kind = m.slab_bytes(grown)
    assert set(by_kind) == {"recurrent", "state", "context"}


# -- (h) what is not implemented says so by mechanism -------------------------
def test_h_what_is_not_implemented_says_so(model):
    with pytest.raises(NotImplementedError, match="recurrent matrix states"):
        model.export_slab_rows(fresh_slab(model), 0, 4)
    with pytest.raises(NotImplementedError, match="recurrent matrix states"):
        model.import_slab_rows(fresh_slab(model), 0, None)
    with pytest.raises(NotImplementedError, match="recurrent matrix states"):
        model._decode_params_quant()
    with pytest.raises(NotImplementedError, match="gated delta rule"):
        model.train_one_batch(None, None)
    with pytest.raises(NotImplementedError, match="recurrent matrix states"):
        model._shard_decode_params(None, None)
