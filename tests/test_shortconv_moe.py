"""The gated short-convolution / grouped-query mixture-of-experts LM
against its plain reference (ISSUE 32), at a small size with the
served configuration's pattern kept: 9 layers (a convolution layer with
the dense MLP, then `full_attention, conv, conv, conv` twice), 3 taps,
2 key/value heads of 8 query heads of 16 dimensions, 16 experts top 4,
all held. Seeded float32 weights on the CPU at "highest": every
tolerance below is float32 rounding through 9 layers (2e-5 of logits
whose scale is about 7 is what is read; 2e-4 leaves that ten times its
room), and no room for a dropped tap, swapped gates, a missing or
misplaced head norm, another theta or weighting by sig + b, each of
which a test below shows moving the logits by 1e-2 and more. The head
norms' gains are drawn away from 1 here (the model draws 1), so that
the norm's place before the rotation can be told.
"""
import functools

import numpy as np
import pytest

from perfbench.reference import lfm2_moe_ref as ref
from singa_tpu import device, serve, stats, tensor
from singa_tpu.models.routed_experts import routed_experts
from singa_tpu.models.shortconv_moe import ShortConvMoELM

LAYERS = ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
ARCH = dict(num_heads=8, kv_heads=2, head_dim=16, conv_L=3, rope_theta=1e6,
            layer_types=LAYERS, num_dense_layers=1, n_experts=16,
            experts_per_token=4, held=[0, 16], router_sum_eps=1e-6,
            norm_eps=1e-5)
V, D = 64, 48
CONVS = [li for li, kind in enumerate(LAYERS) if kind == "conv"]
TOL = dict(rtol=0, atol=2e-4)   # float32 rounding; the logits' scale is ~7


@pytest.fixture(autouse=True)
def _highest():
    before = tensor.get_matmul_precision()
    tensor.set_matmul_precision("highest")
    yield
    tensor.set_matmul_precision(before)


def build(seed=3, **over):
    import jax.numpy as jnp

    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    kw = dict(d_model=D, d_ff=96, d_ff_expert=32, max_len=64,
              init_std=0.3, **ARCH)
    kw.update(over)
    m = ShortConvMoELM(V, **kw)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32), device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    rng = np.random.default_rng(seed)
    for li, kind in enumerate(m.layer_types):
        if kind == "full_attention":
            attn = getattr(m.blocks, f"l{li}").attn
            for gain in (attn.q_norm, attn.k_norm):
                gain.data = jnp.asarray(
                    rng.uniform(0.5, 1.5, gain.data.shape), jnp.float32)
    return m


@pytest.fixture(scope="module")
def model():
    return build()


def states_of(m):
    return {k: v.data for k, v in m.get_states().items()}


def ref_logits(m, ids, **over):
    return np.asarray(ref.logits(states_of(m), np.asarray(ids),
                                 **{**ARCH, **over}))


def ids_of(shape, seed=0):
    return np.random.default_rng(seed).integers(0, V, shape, dtype=np.int32)


def put(a):
    import jax.numpy as jnp

    return jnp.asarray(a)


def fresh_slab(m, slots=2, seq=32):
    import jax

    return m.new_slab(m._decode_params(), slots, seq, jax.devices()[0])


def prefill(m, slab, rows, bucket, slots=None):
    """rows: list of 1-d id arrays -> (logits [len(rows), V], slab)."""
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


def gated_inputs(m, ids):
    """u = b * x of every convolution layer over a whole sequence
    [S, d], from the reference's own forward (float32)."""
    import jax

    seen = []
    real = ref._short_conv

    def spy(u, taps):
        seen.append(np.asarray(u)[0])
        return real(u, taps)

    ref._short_conv = spy
    try:
        with jax.default_matmul_precision("highest"):
            ref.forward(states_of(m), put(np.asarray(ids)[None]),
                        ref._arch(ARCH))
    finally:
        ref._short_conv = real
    return seen


def state_of(slab, li, slot, pos):
    """(u_{pos-2}, u_{pos-1}) of a slot as the slab holds them, for a
    session whose next position is `pos`: u_p lies in column p mod 2."""
    s = np.asarray(slab[li]["u"])[slot]
    return s[pos % 2], s[(pos + 1) % 2]


# -- (a) eval forward = reference ------------------------------------------
@pytest.mark.parametrize("dense_rows", [256, 0], ids=["dense", "sorted"])
def test_a_eval_forward_equals_reference(dense_rows):
    m = build()
    m.dense_rows = dense_rows
    ids = ids_of((2, 21))
    got = m.forward(tensor.from_numpy(ids)).to_numpy()
    np.testing.assert_allclose(got, ref_logits(m, ids), **TOL)


def _variant(model, what, ids, monkeypatch):
    """The reference's logits with one term of the mathematics changed:
    by the weights it is given, by an architecture number, or by one of
    its own small functions swapped (un-jitted, so the swap is seen)."""
    import jax
    import jax.numpy as jnp

    st, over = states_of(model), {}
    if what == "dropped_tap":
        st = {k: (v.at[0].set(0) if k.endswith("conv.w") else v)
              for k, v in st.items()}
    elif what == "b_and_c_swapped":
        st = {k: (jnp.concatenate([v[:, D:2 * D], v[:, :D], v[:, 2 * D:]], 1)
                  if k.endswith("conv.W_in") else v) for k, v in st.items()}
    elif what == "no_head_norm":
        monkeypatch.setattr(ref, "_normed_rotary",
                            lambda x, g, theta, eps: ref._rope(x, theta))
    elif what == "norm_after_rotary":
        monkeypatch.setattr(
            ref, "_normed_rotary",
            lambda x, g, theta, eps: ref._rms(ref._rope(x, theta), g, eps))
    elif what == "theta_1e4":
        over["rope_theta"] = 1e4
    elif what == "bias_weighted_into_the_shares":
        def route(x, W_r, bias, k, sum_eps, idx=None):
            sig = jax.nn.sigmoid(x @ W_r) + bias
            chosen, idx = jax.lax.top_k(sig, k)
            return idx, chosen / (chosen.sum(-1, keepdims=True) + sum_eps)

        monkeypatch.setattr(ref, "_route", route)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(st, put(ids),
                                      ref._arch({**ARCH, **over})))


@pytest.mark.parametrize("what", [
    "dropped_tap", "b_and_c_swapped", "no_head_norm", "norm_after_rotary",
    "theta_1e4", "bias_weighted_into_the_shares"])
def test_a_the_reference_would_notice(model, what, monkeypatch):
    """Each listed term moves the logits by far more than TOL. (The
    `1e-6` of the normalising sum moves a logit by 1e-6 of itself at
    any size a model has: the routed layer's own test below shows it
    where the scores are small enough to see.)"""
    ids = ids_of((1, 21))
    want = ref_logits(model, ids)
    np.testing.assert_allclose(_variant(model, "as_it_is", ids, monkeypatch),
                               want, **TOL)
    other = _variant(model, what, ids, monkeypatch)
    assert np.abs(other - want).max() > 1e-2


# -- (b) prefill then decode through context and state = the full forward ----
@pytest.mark.parametrize("P", [1, 2, 3, 8, 9],
                         ids=["one", "two", "three", "bucket_edge",
                              "one_past_it"])
def test_b_prefill_then_steps_equal_reference(model, P):
    """Prompts shorter than the state (its missing columns are zero),
    as long as it, a whole bucket and one token into the next (7 pad
    positions behind the last real one)."""
    m = model
    full = ids_of((24,), seed=P)
    want = ref_logits(m, full[None])[0]
    bucket = 1 << (P - 1).bit_length()
    lg, slab = prefill(m, fresh_slab(m), [full[:P]], bucket)
    np.testing.assert_allclose(lg[0], want[P - 1], **TOL)
    for t in range(P, len(full)):
        out, slab = step(m, slab, [full[t], 0], [t, 0])
        np.testing.assert_allclose(out[0], want[t], **TOL)


def test_b_scan_blocks_equal_single_steps(model):
    """A run-ahead block is the same greedy steps in one program: its
    tokens are the reference's argmax along the sequence it makes, its
    slab (contexts and states) the one k single steps leave."""
    m = model
    params = m._decode_params()
    prompt = ids_of((6,), seed=9)
    lg, slab = prefill(m, fresh_slab(m), [prompt], 8)
    tok = np.array([lg[0].argmax(), 0], np.int32)
    pos = np.array([6, 0], np.int32)
    _, slab2 = prefill(m, fresh_slab(m), [prompt], 8)
    seq = list(prompt) + [int(tok[0])]
    for k in (3, 8):       # an odd block: the states' columns swap roles
        toks, slab = m.decode_scan(params, slab, put(tok), put(pos), k)
        toks = np.asarray(toks)
        t1 = tok.copy()
        for s in range(k):
            out, slab2 = step(m, slab2, t1, pos + s)
            t1 = out.argmax(-1).astype(np.int32)
            assert t1[0] == toks[s, 0]
        seq += [int(t) for t in toks[:, 0]]
        tok, pos = toks[-1].astype(np.int32), pos + k
    for a, b in zip(slab, slab2):
        assert set(a) == set(b)
        for n in a:
            np.testing.assert_allclose(np.asarray(a[n])[0],
                                       np.asarray(b[n])[0], **TOL)
    want = ref_logits(m, np.asarray(seq)[None])[0]
    short = want.max(-1)[6:-1] - want[np.arange(6, len(seq) - 1), seq[7:]]
    assert short.max() < 2e-4      # greedy by the reference too


# -- (c) a cohort with mixed real lengths in one bucket -----------------------
def test_c_cohort_prefill_writes_each_state_from_its_last_two_real_positions(
        model):
    """One bucket of 16 holds prompts of 1, 2, 11 and 16 tokens: each
    row reads its own last real token, and each convolution state holds
    u at the row's positions n-2 and n-1 (zero where there is none),
    never the pad tail's; the next step's logits need both."""
    m = model
    lens, slots = (1, 2, 11, 16), [2, 0, 3, 1]
    rows = [ids_of((n,), seed=n) for n in lens]
    lg, slab = prefill(m, fresh_slab(m, slots=4), rows, 16, slots=slots)
    for row, slot in zip(rows, slots):
        n = len(row)
        for li, u in zip(CONVS, gated_inputs(m, row)):
            older, newer = state_of(slab, li, slot, n)
            np.testing.assert_allclose(newer, u[n - 1], **TOL)
            np.testing.assert_allclose(
                older, u[n - 2] if n > 1 else 0 * u[0], **TOL)
    nxt = ids_of((4,), seed=5)
    want = [ref_logits(m, np.concatenate([r, [t]])[None])[0]
            for r, t in zip(rows, nxt)]
    for r, row in enumerate(rows):
        np.testing.assert_allclose(lg[r], want[r][len(row) - 1], **TOL)
    tok, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for slot, row, t in zip(slots, rows, nxt):
        tok[slot], pos[slot] = t, len(row)
    out, _ = step(m, slab, tok, pos)
    for slot, w_ in zip(slots, want):
        np.testing.assert_allclose(out[slot], w_[-1], **TOL)


def test_c_a_pad_row_writes_nothing(model):
    m = model
    _, slab = prefill(m, fresh_slab(m), [ids_of((7,)), ids_of((3,), 1)], 8)
    before = [{n: np.asarray(a) for n, a in c.items()} for c in slab]
    _, slab = prefill(m, slab, [ids_of((5,), 2)], 8, slots=[2])  # no such slot
    for b, c in zip(before, slab):
        assert set(b) == set(c)
        for n in b:
            assert np.array_equal(b[n], np.asarray(c[n]))


# -- (d) a slot's next session sees nothing of its last -----------------------
def test_d_a_reused_slot_starts_from_its_own_prompt_alone(model):
    """Slot 0 holds a session 13 positions in; a one-token prompt is
    then prefilled into it. Its states are (0, u_0) of the new prompt
    (a state the prefill only wrote where the prompt had positions
    would keep the old session's u in the other column), the context's
    old tail is behind the mask, and the stream is the reference's."""
    m = model
    old = ids_of((13,), seed=21)
    _, slab = prefill(m, fresh_slab(m), [old[:9]], 16)
    for t in range(9, 13):
        _, slab = step(m, slab, [old[t], 0], [t, 0])
    assert all(np.asarray(slab[li]["u"])[0].all() for li in CONVS)
    new = ids_of((12,), seed=22)
    want = ref_logits(m, new[None])[0]
    lg, slab = prefill(m, slab, [new[:1]], 1)
    for li, u in zip(CONVS, gated_inputs(m, new[:1])):
        older, newer = state_of(slab, li, 0, 1)
        assert not older.any()
        np.testing.assert_allclose(newer, u[0], **TOL)
    np.testing.assert_allclose(lg[0], want[0], **TOL)
    for t in range(1, len(new)):
        out, slab = step(m, slab, [new[t], 0], [t, 0])
        np.testing.assert_allclose(out[0], want[t], **TOL)


def _serve(m, requests, together=True, **kw):
    eng = serve.ServingEngine(m, max_sessions=2, max_new_tokens=24,
                              prefill_batch=2, decode_block=4, **kw).start()
    try:
        eng.warm_decode(prompt_lens=(1, 16), max_new_tokens=24)
        out = []
        replies = []
        for p, n in requests:
            replies.append(eng.submit_decode(p, n))
            if not together:     # one at a time: each takes slot 0
                out.append(np.asarray(replies[-1].result(timeout=300))[0])
        if together:
            out = [np.asarray(r.result(timeout=300))[0] for r in replies]
        return out
    finally:
        eng.stop()


def test_d_a_second_session_in_a_slot_streams_what_it_streams_alone(model):
    """Through `ServingEngine`: three requests one after another all
    take slot 0 (lowest free index first); each streams what the same
    request streams in an engine of its own, and what the reference
    picks. The gauges and counters are the model's: a state entry a
    convolution layer, every assignment local."""
    m = model
    requests = [(ids_of((13,), 11), 20), (ids_of((1,), 12), 24),
                (ids_of((2,), 13), 9)]
    stats.reset_cache_stats()
    in_turn = _serve(m, requests, together=False)
    d = stats.cache_stats()["decode"]
    assert d["host_leaves_per_call"] == 0 and d["cache_bytes_ring"] == 0
    # 7 convolution layers x 2 slots x 2 columns x 48 x 4 B
    assert d["cache_bytes_state"] == 7 * 2 * 2 * D * 4
    # 2 attention layers x 2 slots x (k + v) x 2 heads x 16 x rung x 4 B
    assert d["cache_bytes_context"] % (2 * 2 * 2 * 2 * 16 * 4) == 0
    assert d["cache_bytes_context"] > 0
    # all 16 experts held: every row's 4 assignments are local, in each
    # of 8 routed layers, whether its slot is live or not
    assert d["moe_assignments_local"] == 8 * 2 * 4 * d["decode_steps"]
    assert 0 < d["moe_experts_touched"] <= 8 * 8 * d["decode_steps"]
    for (prompt, n), got in zip(requests, in_turn):
        assert len(got) == len(prompt) + n
        assert np.array_equal(got, _serve(m, [(prompt, n)])[0])
        want = ref_logits(m, got[None])[0]
        at = np.arange(len(prompt) - 1, len(got) - 1)
        assert (want[at].max(-1) - want[at, got[at + 1]]).max() < 2e-4
    beside = _serve(m, requests[:2])
    for got, alone in zip(beside, in_turn):
        assert np.array_equal(got, alone)


# -- (e) growth: only what holds the context climbs the ladder ----------------
def test_e_growth_leaves_states_alone_and_streams_unchanged(model):
    m = model
    full = ids_of((28,), seed=4)
    want = ref_logits(m, full[None])[0]
    _, slab = prefill(m, fresh_slab(m, seq=16), [full[:10]], 16)
    for t in range(10, 16):
        _, slab = step(m, slab, [full[t], 0], [t, 0])
    grown = m.grow_slab(slab, 32)
    assert m.slab_dims(slab) == (2, 16) and m.slab_dims(grown) == (2, 32)
    for kind, old, new in zip(m.layer_types, slab, grown):
        if kind == "conv":
            assert set(new) == {"u"} and new["u"] is old["u"]
        else:
            for n in ("k", "v"):
                assert new[n].shape == (2, 2, 16, 32)
                assert np.array_equal(np.asarray(new[n])[..., :16],
                                      np.asarray(old[n]))
                assert not np.asarray(new[n])[..., 16:].any()
    by_kind = m.slab_bytes(grown)
    assert set(by_kind) == {"context", "state"}
    assert by_kind["state"] == m.slab_bytes(slab)["state"] \
        == 7 * 2 * 2 * D * 4
    assert by_kind["context"] == 2 * m.slab_bytes(slab)["context"]
    assert m._slab_sig(grown) != m._slab_sig(slab)
    assert m._slab_extra(grown)[0] == [[2, 2, D]]
    slab = grown
    for t in range(16, 28):
        out, slab = step(m, slab, [full[t], 0], [t, 0])
        np.testing.assert_allclose(out[0], want[t], **TOL)


# -- (f) the routed layer with every expert held -------------------------------
@pytest.mark.parametrize("dense_rows", [256, 0], ids=["dense", "sorted"])
@pytest.mark.parametrize("spread", ["one_expert", "four_experts"])
def test_f_no_token_dropped_at_any_imbalance_with_all_experts_held(
        spread, dense_rows):
    """Every token routed to the same four experts (a capacity factor
    would drop most of them), or three quarters of each token's share
    to experts that weigh nothing and all rows to ONE expert that
    does: the sorted path goes straight through all N * K assignments
    (no quarter-rows branch exists where every expert is held)."""
    import jax

    rng = np.random.default_rng(2)
    E, f, N, K = 16, 32, 300, 4
    b = np.zeros(E, np.float32)
    b[[5, 0, 1, 2]] = [10, 9, 8, 7]
    w = {k: rng.normal(0, 0.3, s).astype(np.float32) for k, s in
         (("W_g", (E, D, f)), ("W_u", (E, D, f)), ("W_d", (E, f, D)))}
    live = [5, 0, 1, 2]
    if spread == "one_expert":
        for e in (0, 1, 2):
            w["W_d"][e] = 0
        live = [5]
    x = rng.normal(0, 1, (N, D)).astype(np.float32)
    ffn = {"W_r": put(np.zeros((D, E), np.float32)), "b": put(b),
           **{k: put(v) for k, v in w.items()}}
    fn = functools.partial(routed_experts, held=(0, E), experts_per_token=K,
                           dense_rows=dense_rows, sum_eps=1e-6)
    y, counts = fn(ffn, put(x), "highest")
    want = np.zeros((N, D))
    for e in live:            # sig = 0.5 everywhere: shares of 1/4
        g, u = x @ w["W_g"][e], x @ w["W_u"][e]
        want += 0.25 * ((g / (1 + np.exp(-g)) * u) @ w["W_d"][e])
    np.testing.assert_allclose(np.asarray(y), want, **TOL)
    counts = np.asarray(counts)
    assert counts.sum() == N * K and counts.max() == N
    assert (counts > 0).sum() == 4
    if not dense_rows:
        text = str(jax.make_jaxpr(lambda f_, x_: fn(f_, x_, "highest"))(
            ffn, put(x)))
        assert "ragged_dot" in text and "cond[" not in text
        # where a share is held, the quarter-rows branch is compiled
        half = {**ffn, **{k: ffn[k][:8] for k in ("W_g", "W_u", "W_d")}}
        text = str(jax.make_jaxpr(lambda f_, x_: routed_experts(
            f_, x_, "highest", held=(0, 8), experts_per_token=K,
            dense_rows=0))(half, put(x)))
        assert "ragged_dot" in text and "cond[" in text


@pytest.mark.parametrize("dense_rows", [256, 0], ids=["dense", "sorted"])
def test_f_the_normalising_epsilon_is_an_architecture_number(dense_rows):
    """w_e = sig_e / (sum_S sig + eps): at scores of 1e-6 the four
    shares of a token add up to 0.8 with the published 1e-6 and to 1
    with none, which is what tells the two models' layers apart."""
    rng = np.random.default_rng(3)
    E, f, N, K = 16, 32, 40, 4
    x = rng.normal(0, 1, (N, D)).astype(np.float32)
    x[:, 0] = 1.0
    W_r = rng.normal(0, 0.01, (D, E)).astype(np.float32)
    W_r[0] = -13.8                      # sigmoid(-13.8) = 1.0e-6
    w = {k: rng.normal(0, 0.3, s).astype(np.float32) for k, s in
         (("W_g", (E, D, f)), ("W_u", (E, D, f)), ("W_d", (E, f, D)))}
    ffn = {"W_r": put(W_r), "b": put(np.zeros(E, np.float32)),
           **{k: put(v) for k, v in w.items()}}

    def by_hand(eps):
        sig = 1 / (1 + np.exp(-(x.astype(np.float64) @ W_r)))
        idx = np.argsort(-sig, -1, kind="stable")[:, :K]
        out = np.zeros((N, D))
        for n in range(N):
            for e in idx[n]:
                g, u = x[n] @ w["W_g"][e], x[n] @ w["W_u"][e]
                out[n] += sig[n, e] / (sig[n, idx[n]].sum() + eps) * (
                    (g / (1 + np.exp(-g)) * u) @ w["W_d"][e])
        return out

    got = {eps: np.asarray(routed_experts(
        ffn, put(x), "highest", held=(0, E), experts_per_token=K,
        dense_rows=dense_rows, sum_eps=eps)[0]) for eps in (1e-6, 0.0)}
    for eps in (1e-6, 0.0):
        np.testing.assert_allclose(got[eps], by_hand(eps), rtol=0, atol=2e-3)
    scale = np.abs(got[0.0]).max()
    assert scale > 1 and np.abs(got[1e-6] - got[0.0]).max() > 0.1 * scale


# -- what is not implemented says so, by mechanism ------------------------------
def test_unimplemented_mechanisms_raise_by_name(model):
    m = model
    with pytest.raises(NotImplementedError,
                       match="no training path.*short convolution"):
        m.train_one_batch(None, None)
    with pytest.raises(NotImplementedError, match="no training path"):
        m.compile([], is_train=True)
    with pytest.raises(NotImplementedError, match="mesh / ParallelPlan"):
        m.compile([], mesh=object())
    with pytest.raises(NotImplementedError,
                       match="int8 decode tier.*convolution states"):
        m._decode_params_quant()
    with pytest.raises(NotImplementedError, match="KV export"):
        m.export_slab_rows(fresh_slab(m), 0, 1)
    with pytest.raises(NotImplementedError,
                       match="KV import.*convolution states"):
        m.import_slab_rows(fresh_slab(m), 0, None)
    with pytest.raises(NotImplementedError,
                       match="tensor-parallel.*convolution state"):
        m._shard_decode_params(m._decode_params(), None)
    device.set_inference_quant("int8")
    try:
        eng = serve.ServingEngine(m, max_sessions=2, max_new_tokens=4)
        with pytest.raises(NotImplementedError, match="int8 decode tier"):
            eng.start().warm_decode(prompt_lens=(4,), max_new_tokens=4)
    finally:
        eng.stop()
        device.set_inference_quant("off")
    with pytest.raises(ValueError, match="no full_attention layer"):
        ShortConvMoELM(V, layer_types=("conv", "conv"))
    with pytest.raises(ValueError, match="no range of 16 experts"):
        ShortConvMoELM(V, n_experts=16, held=(8, 16))


def test_bfloat16_parameters_are_drawn_in_place():
    """The served configuration stores bfloat16: every matrix and the
    taps are born in it on the device (norm gains and the router stay
    float32), the slab takes the embedding's dtype, and the forward
    agrees with the float32 reference on the same bfloat16 values to
    bfloat16's 8 bits at the median position (at a router near-tie a
    rounded activation picks another expert)."""
    import jax.numpy as jnp

    m = build(param_dtype="bfloat16")
    st = states_of(m)
    for name, dtype in (("embed.W", jnp.bfloat16),
                        ("blocks.l0.conv.w", jnp.bfloat16),
                        ("blocks.l0.conv.W_in", jnp.bfloat16),
                        ("blocks.l1.attn.W_qkv", jnp.bfloat16),
                        ("blocks.l1.attn.q_norm", jnp.float32),
                        ("blocks.l1.moe.W_g", jnp.bfloat16),
                        ("blocks.l1.moe.W_r", jnp.float32),
                        ("blocks.l1.moe.b", jnp.float32),
                        ("ln_f.gamma", jnp.float32)):
        assert st["ShortConvMoELM." + name].dtype == dtype, name
    assert "ShortConvMoELM.head.W" not in st            # tied
    assert {a.dtype for c in fresh_slab(m) for a in c.values()} \
        == {jnp.dtype(jnp.bfloat16)}
    ids = ids_of((1, 12))
    got = m.forward(tensor.from_numpy(ids)).to_numpy().astype(np.float32)
    want = ref_logits(m, ids)
    worst_by_position = np.abs(got - want).max(-1)
    assert np.median(worst_by_position) < 0.05 * np.abs(want).max()
