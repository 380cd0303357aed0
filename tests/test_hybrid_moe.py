"""The hybrid window/full mixture-of-experts LM against its plain
reference (ISSUE 27), at a small size with every ratio of the served
configuration kept: 2 full + 5 window layers, window 8, 2 / 4
key-value heads of 8 query heads, head sizes 24 / 16 with 8 rotary
dimensions, 32 experts top 4, 4 held. Seeded float32 weights on the
CPU at "highest": every tolerance below is float32 rounding through 7
layers (1e-4 of logits whose scale is about 5), which leaves no room
for a dropped sink (a test below moves logits by 1e-2 and more), a
wrong theta, or weighting by sig + b.
"""
import numpy as np
import pytest

from perfbench.reference import mimo_v2_ref as ref
from singa_tpu import device, serve, stats, tensor
from singa_tpu.models.hybrid_moe import HybridWindowMoELM

ARCH = dict(num_heads=8, head_dim=24, v_head_dim=16, kv_heads_full=2,
            kv_heads_window=4, window=8, rotary_dim=8,
            rope_theta_full=1e7, rope_theta_window=1e4, value_scale=0.707,
            layer_pattern=[0, 1, 1, 1, 1, 1, 0],
            moe_layers=[0, 1, 1, 1, 1, 1, 1], n_experts=32,
            experts_per_token=4, held=[4, 4], norm_eps=1e-5)
V, D = 64, 48
W = ARCH["window"]
TOL = dict(rtol=0, atol=2e-4)   # float32 rounding; the logits' scale is ~5


@pytest.fixture(autouse=True)
def _highest():
    before = tensor.get_matmul_precision()
    tensor.set_matmul_precision("highest")
    yield
    tensor.set_matmul_precision(before)


def build(seed=3, **over):
    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    kw = dict(d_model=D, d_ff=96, d_ff_expert=32, max_len=64,
              init_std=0.3, **ARCH)
    kw.update(over)
    m = HybridWindowMoELM(V, **kw)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32), device=dev)],
              is_train=False, use_graph=False)
    m.eval()
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


# -- (a) eval forward = reference ------------------------------------------
@pytest.mark.parametrize("dense_rows", [256, 0], ids=["dense", "sorted"])
def test_a_eval_forward_equals_reference(dense_rows):
    m = build()
    m.dense_rows = dense_rows
    ids = ids_of((2, 21))
    got = m.forward(tensor.from_numpy(ids)).to_numpy()
    np.testing.assert_allclose(got, ref_logits(m, ids), **TOL)


def test_a_a_long_prompt_goes_by_chunks_of_queries():
    """Past 256 tokens a full layer attends by chunks of 256 queries,
    each over the keys up to its own last query (the served sizes'
    path: 2 to 8 chunks a prompt)."""
    m = build(max_len=512)
    ids = ids_of((2, 512), seed=5)
    got = m.forward(tensor.from_numpy(ids)).to_numpy()
    # 512 keys a softmax, not 21: twice the rounding of the short case
    np.testing.assert_allclose(got, ref_logits(m, ids), rtol=0, atol=4e-4)


@pytest.mark.parametrize("what", ["sink", "theta", "bias", "scale"])
def test_a_the_reference_would_notice(model, what):
    """Each assumed mechanism moves the logits by far more than TOL:
    a dropped sink, the full layers' theta in the window layers,
    weighting by sig + b, and an unscaled v."""
    ids = ids_of((1, 21))
    want = ref_logits(model, ids)
    st = states_of(model)
    over = {}
    if what == "sink":
        st = {k: (v * 0 - 30.0 if k.endswith("attn.sink") else v)
              for k, v in st.items()}
    elif what == "theta":
        over["rope_theta_window"] = ARCH["rope_theta_full"]
    elif what == "bias":
        st = {k: (v * 0 if k.endswith("moe.b") else v)
              for k, v in st.items()}
    else:
        over["value_scale"] = 1.0
    other = np.asarray(ref.logits(st, ids, **{**ARCH, **over}))
    assert np.abs(other - want).max() > 1e-2


# -- (b) prefill then decode through the slab = the full forward -------------
@pytest.mark.parametrize("P", [3, 8, 13], ids=["under", "window", "over"])
def test_b_prefill_then_steps_equal_reference(model, P):
    """Contexts under and over the window; 30 positions wrap a ring of
    8 three times."""
    m = model
    full = ids_of((30,), seed=P)
    want = ref_logits(m, full[None])[0]
    bucket = 1 << (P - 1).bit_length()
    lg, slab = prefill(m, fresh_slab(m), [full[:P]], bucket)
    np.testing.assert_allclose(lg[0], want[P - 1], **TOL)
    params = m._decode_params()
    for t in range(P, len(full)):
        out, slab = m.decode_step(params, slab, put(np.array(
            [full[t], 0], np.int32)), put(np.array([t, 0], np.int32)))
        np.testing.assert_allclose(np.asarray(out)[0], want[t], **TOL)


def test_b_scan_blocks_equal_single_steps(model):
    """A run-ahead block is the same greedy steps in one program: its
    tokens are the reference's argmax along the sequence it makes, its
    slab the one k single steps leave, across a ring wrap."""
    m = model
    params = m._decode_params()
    prompt = ids_of((6,), seed=9)
    lg, slab = prefill(m, fresh_slab(m), [prompt], 8)
    tok = np.array([lg[0].argmax(), 0], np.int32)
    pos = np.array([6, 0], np.int32)
    lg2, slab2 = prefill(m, fresh_slab(m), [prompt], 8)
    seq = list(prompt) + [int(tok[0])]
    for k in (4, 8):
        toks, slab = m.decode_scan(params, slab, put(tok), put(pos), k)
        toks = np.asarray(toks)
        t1 = tok.copy()
        for s in range(k):
            out, slab2 = m.decode_step(params, slab2, put(t1),
                                       put(pos + s))
            t1 = np.asarray(out).argmax(-1).astype(np.int32)
            assert t1[0] == toks[s, 0]
        seq += [int(t) for t in toks[:, 0]]
        tok, pos = toks[-1].astype(np.int32), pos + k
    for a, b in zip(slab, slab2):
        for n in ("k", "v"):
            np.testing.assert_allclose(np.asarray(a[n])[0],
                                       np.asarray(b[n])[0], **TOL)
    want = ref_logits(m, np.asarray(seq)[None])[0]
    short = want.max(-1)[6:-1] - want[np.arange(6, len(seq) - 1), seq[7:]]
    assert short.max() < 2e-4      # greedy by the reference too


# -- (c) a cohort with mixed real lengths in one bucket -----------------------
def test_c_cohort_prefill_mixed_lengths_fills_each_ring(model):
    """Shorter than the window, longer than it but shorter than the
    bucket, and the whole bucket: each row reads its own last real
    token, and its ring holds its last min(n, 8) REAL tokens (the next
    step's logits need every one of them)."""
    m = model
    rows = [ids_of((n,), seed=n) for n in (3, 11, 16)]
    lg, slab = prefill(m, fresh_slab(m, slots=4), rows, 16,
                       slots=[2, 0, 3])
    nxt = ids_of((3,), seed=5)
    want = [ref_logits(m, np.concatenate([r, [t]])[None])[0]
            for r, t in zip(rows, nxt)]
    for r, row in enumerate(rows):
        np.testing.assert_allclose(lg[r], want[r][len(row) - 1], **TOL)
    tok, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for slot, row, t in zip([2, 0, 3], rows, nxt):
        tok[slot], pos[slot] = t, len(row)
    out, _ = m.decode_step(m._decode_params(), slab, put(tok), put(pos))
    for slot, w_ in zip([2, 0, 3], want):
        np.testing.assert_allclose(np.asarray(out)[slot], w_[-1], **TOL)


def test_c_a_pad_row_writes_nothing(model):
    m = model
    slab = fresh_slab(m)
    before = [{n: np.asarray(a) for n, a in c.items()} for c in slab]
    _, slab = prefill(m, slab, [ids_of((5,))], 8, slots=[2])   # out of bounds
    for b, c in zip(before, slab):
        for n in ("k", "v"):
            assert np.array_equal(b[n], np.asarray(c[n]))


# -- (d) growth: only what holds the context climbs the ladder ----------------
def test_d_growth_leaves_rings_alone_and_streams_unchanged(model):
    m = model
    params = m._decode_params()
    full = ids_of((28,), seed=4)
    want = ref_logits(m, full[None])[0]
    _, slab = prefill(m, fresh_slab(m, seq=16), [full[:10]], 16)
    for t in range(10, 16):
        out, slab = m.decode_step(params, slab, put(np.array(
            [full[t], 0], np.int32)), put(np.array([t, 0], np.int32)))
    rings = [np.asarray(c["k"]) for kind, c in zip(m.layer_pattern, slab)
             if kind == 1]
    grown = m.grow_slab(slab, 32)
    assert m.slab_dims(grown) == (2, 32)
    for kind, old, new in zip(m.layer_pattern, slab, grown):
        if kind == 1:
            assert new["k"] is old["k"] and new["v"] is old["v"]
        else:
            assert new["k"].shape[3] == new["v"].shape[2] == 32
            assert np.array_equal(np.asarray(new["k"])[..., :16],
                                  np.asarray(old["k"]))
    by_kind = m.slab_bytes(grown)
    assert by_kind["ring"] == m.slab_bytes(slab)["ring"] == sum(
        2 * 4 * W * (24 + 16) * 4 for _ in range(5))
    assert by_kind["context"] == 2 * m.slab_bytes(slab)["context"]
    slab = grown
    for t in range(16, 28):
        out, slab = m.decode_step(params, slab, put(np.array(
            [full[t], 0], np.int32)), put(np.array([t, 0], np.int32)))
        np.testing.assert_allclose(np.asarray(out)[0], want[t], **TOL)
    assert len(rings) == 5


# -- (e) the shares add up ------------------------------------------------------
def _uncut_layer(x, ffn, K, eps):
    """One routed layer over ALL experts in numpy float64."""
    x = x.astype(np.float64)
    sig = 1 / (1 + np.exp(-(x @ ffn["W_r"].astype(np.float64))))
    idx = np.argsort(-(sig + ffn["b"]), -1, kind="stable")[:, :K]
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        share = sig[n, idx[n]] / (sig[n, idx[n]].sum() + eps)
        for e, w_ in zip(idx[n], share):
            g = x[n] @ ffn["W_g"][e].astype(np.float64)
            u = x[n] @ ffn["W_u"][e].astype(np.float64)
            out[n] += w_ * ((g / (1 + np.exp(-g)) * u)
                            @ ffn["W_d"][e].astype(np.float64))
    return out


def _shortconv(n_experts):
    """The other model that calls the one routed layer
    (`models/routed_experts.py`), at this file's width."""
    from singa_tpu.models.shortconv_moe import ShortConvMoELM

    return ShortConvMoELM(V, d_model=D, d_ff_expert=32, n_experts=n_experts,
                          experts_per_token=4, held=(0, n_experts),
                          layer_types=("conv", "full_attention"),
                          num_dense_layers=1)


# (the model, its experts, the experts a share holds, the epsilon of
# its normalising sum): mimo-v2.5's eighths of 32 through
# `HybridWindowMoELM`, lfm2-24b-a2b's published 64 through
# `ShortConvMoELM`, whose served `held` is all of them
NUMBERS = {"hybrid_8_shares_of_4": (build, 32, 4, 0.0),
           "shortconv_8_shares_of_8": (lambda: _shortconv(64), 64, 8, 1e-6)}


@pytest.mark.parametrize("dense_rows", [256, 0], ids=["dense", "sorted"])
@pytest.mark.parametrize("numbers", list(NUMBERS))
def test_e_the_eight_shares_sum_to_the_uncut_layer(numbers, dense_rows):
    """held = each eighth of the experts: the eight partial results add
    up to the whole layer (nothing is computed twice: no shared
    expert), whichever model's numbers go through the one shared
    routed layer; eight shares of 8 sum to `held = (0, 64)`, which is
    what the served lfm2 configuration holds."""
    make, E, share, eps = NUMBERS[numbers]
    rng = np.random.default_rng(1)
    m = make()
    m.dense_rows = dense_rows
    f = 32
    ffn = {"W_r": rng.normal(0, 0.3, (D, E)).astype(np.float32),
           "b": rng.normal(0, 0.1, E).astype(np.float32),
           "W_g": rng.normal(0, 0.3, (E, D, f)).astype(np.float32),
           "W_u": rng.normal(0, 0.3, (E, D, f)).astype(np.float32),
           "W_d": rng.normal(0, 0.3, (E, f, D)).astype(np.float32)}
    x = rng.normal(0, 1, (37, D)).astype(np.float32)
    whole = _uncut_layer(x, ffn, 4, eps)
    total, counted = 0, 0
    for first in range(0, E, share):
        m.held = (first, share)
        part = {"W_r": put(ffn["W_r"]), "b": put(ffn["b"]),
                **{k: put(ffn[k][first:first + share])
                   for k in ("W_g", "W_u", "W_d")}}
        y, counts = m._experts(part, put(x), "highest")
        total = total + np.asarray(y, np.float64)
        counted += int(np.asarray(counts).sum())
    assert counted == 37 * 4                    # every assignment, once
    np.testing.assert_allclose(total, whole, **TOL)
    if share * 8 == E == 64:
        m.held = (0, E)
        y, counts = m._experts({k: put(v) for k, v in ffn.items()}, put(x),
                               "highest")
        np.testing.assert_allclose(np.asarray(y), whole, **TOL)
        assert int(np.asarray(counts).sum()) == 37 * 4


# -- (f) adversarial routing: nothing is dropped -------------------------------
@pytest.mark.parametrize("dense_rows", [256, 0], ids=["dense", "sorted"])
@pytest.mark.parametrize("spread", ["one_held_expert", "all_four_held"])
def test_f_no_token_dropped_at_any_imbalance(spread, dense_rows):
    """Every token routed to ONE held expert (a capacity factor would
    drop most of them), and every token's four experts all held (the
    sorted buffer full to its last row)."""
    rng = np.random.default_rng(2)
    m = build()
    m.dense_rows = dense_rows
    first, E = m.held
    b = np.zeros(32, np.float32)
    if spread == "one_held_expert":
        b[[first + 1, 0, 1, 2]] = [10, 9, 8, 7]      # 0..2 live elsewhere
        local = [1]
    else:
        b[first:first + E] = [10, 9, 8, 7]
        local = [0, 1, 2, 3]
    f, N = 32, 300
    w = {k: rng.normal(0, 0.3, s).astype(np.float32) for k, s in
         (("W_g", (E, D, f)), ("W_u", (E, D, f)), ("W_d", (E, f, D)))}
    x = rng.normal(0, 1, (N, D)).astype(np.float32)
    ffn = {"W_r": put(np.zeros((D, 32), np.float32)), "b": put(b),
           **{k: put(v) for k, v in w.items()}}
    y, counts = m._experts(ffn, put(x), "highest")
    want = np.zeros((N, D))
    for e in local:                 # sig = 0.5 everywhere: shares of 1/4
        g, u = x @ w["W_g"][e], x @ w["W_u"][e]
        want += 0.25 * ((g / (1 + np.exp(-g)) * u) @ w["W_d"][e])
    np.testing.assert_allclose(np.asarray(y), want, **TOL)
    counts = np.asarray(counts)
    assert counts.sum() == N * len(local) and counts.max() == N
    assert (counts > 0).sum() == len(local)


# -- (g) served beside other sessions = served alone ----------------------------
def _serve(m, requests, **kw):
    eng = serve.ServingEngine(m, max_sessions=4, max_new_tokens=24,
                              prefill_batch=2, decode_block=4, **kw).start()
    try:
        eng.warm_decode(prompt_lens=(4, 16), max_new_tokens=24)
        replies = [eng.submit_decode(p, n) for p, n in requests]
        return [np.asarray(r.result(timeout=300))[0] for r in replies]
    finally:
        eng.stop()


def test_g_a_stream_beside_others_equals_the_same_request_alone(model):
    m = model
    requests = [(ids_of((5,), 11), 20), (ids_of((13,), 12), 9),
                (ids_of((3,), 13), 24), (ids_of((16,), 14), 16)]
    stats.reset_cache_stats()
    together = _serve(m, requests)
    d = stats.cache_stats()["decode"]
    assert d["host_leaves_per_call"] == 0
    # 5 window layers x 4 slots x 4 heads x 8 positions x (24 + 16) x 4 B
    assert d["cache_bytes_ring"] == 5 * 4 * 4 * W * (24 + 16) * 4
    assert d["cache_bytes_context"] > 0
    # 4 of 32 experts held, 4 of them a token: half an assignment a
    # row a layer, every slot's row counted whether it is live or not
    assert d["moe_assignments_local"] > 0
    assert 0 < d["moe_experts_touched"] <= 6 * 4 * d["decode_steps"]
    assert (d["moe_expert_load_max"] <= d["moe_assignments_local"]
            <= 6 * 4 * 4 * d["decode_steps"])
    for (prompt, n), got in zip(requests, together):
        assert len(got) == len(prompt) + n
        alone = _serve(m, [(prompt, n)])[0]
        assert np.array_equal(got, alone)
        want = ref_logits(m, got[None])[0]
        at = np.arange(len(prompt) - 1, len(got) - 1)
        assert (want[at].max(-1) - want[at, got[at + 1]]).max() < 2e-4


# -- what is not implemented says so, by mechanism ------------------------------
def test_unimplemented_mechanisms_raise_by_name(model):
    m = model
    with pytest.raises(NotImplementedError, match="no training path"):
        m.train_one_batch(None, None)
    with pytest.raises(NotImplementedError, match="int8 decode tier"):
        m._decode_params_quant()
    with pytest.raises(NotImplementedError, match="KV export"):
        m.export_slab_rows(fresh_slab(m), 0, 1)
    with pytest.raises(NotImplementedError, match="KV import"):
        m.import_slab_rows(fresh_slab(m), 0, None)
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        m._shard_decode_params(m._decode_params(), None)
    device.set_inference_quant("int8")
    try:
        eng = serve.ServingEngine(m, max_sessions=2, max_new_tokens=4)
        with pytest.raises(NotImplementedError, match="int8 decode tier"):
            eng.start().warm_decode(prompt_lens=(4,), max_new_tokens=4)
    finally:
        eng.stop()
        device.set_inference_quant("off")


def test_bfloat16_parameters_are_drawn_in_place():
    """The served configuration stores bfloat16: every matrix is born
    in it on the device (norm gains, the sink and the router stay
    float32), and the forward agrees with the float32 reference on the
    same bfloat16 values to bfloat16's 8 bits at most positions (at a
    near-tie of the router a rounded activation picks another expert,
    which at 4 held of 32 and this size moves a position's logits by a
    tenth of their scale: the median position is what is held)."""
    import jax.numpy as jnp

    m = build(param_dtype="bfloat16")
    st = states_of(m)
    assert st["HybridWindowMoELM.blocks.l1.moe.W_g"].dtype == jnp.bfloat16
    assert st["HybridWindowMoELM.embed.W"].dtype == jnp.bfloat16
    assert st["HybridWindowMoELM.blocks.l1.moe.W_r"].dtype == jnp.float32
    ids = ids_of((1, 12))
    got = m.forward(tensor.from_numpy(ids)).to_numpy().astype(np.float32)
    want = ref_logits(m, ids)
    worst_by_position = np.abs(got - want).max(-1)
    assert np.median(worst_by_position) < 0.05 * np.abs(want).max()


# -- (h) GPT-2's slab as the model states it, and its streams -------------------
@pytest.mark.parametrize("quant", ["off", "int8"])
def test_h_gpt2_slab_and_streams_as_before_the_model_stated_them(gpt2,
                                                                  quant):
    """`serve.py` asks the model for the slab. `TransformerLM` answers
    with L buffers [2, slots, H, D, rung], positions last (PR 28), in
    the embedding's dtype (int8 payload + float32 [2, slots, rung]
    scale planes under the int8 tier). Its programs donate the slab
    like the hybrid model's, so after warm-up the engine holds the
    slab the last warm program returned, and a served stream is
    `generate()` bit for bit through growth to the next rung."""
    import jax.numpy as jnp

    m = gpt2
    assert m.step_counter_names == ("attn_blocks_read", "attn_blocks_rung")
    assert m.scan_unroll == 1
    device.set_inference_quant(quant)
    eng = serve.ServingEngine(m, max_sessions=3, max_new_tokens=40,
                              prefill_batch=2, decode_block=4).start()
    try:
        eng.warm_decode(prompt_lens=(5,), max_new_tokens=8)
        slab = eng._slab
        # one slot a session: 3, not the next power of two
        assert len(slab) == 3 and eng._slab_dims() == (3, 16)
        assert eng._decode_geom()[1:] == (3, 16)
        for layer_ in slab:
            pay = layer_[0] if quant == "int8" else layer_
            assert pay.shape == (2, 3, 2, 16, 16)     # [2, B, H, D, T]
            assert not pay.is_deleted()
            # warm-up's longest block wrote positions 0..3 of every
            # row (stale state no query attends), and a pad row's
            # prefill (slot out of bounds) wrote nothing
            assert not np.asarray(pay)[..., 4:].any()
            if quant == "int8":
                assert pay.dtype == jnp.int8
                assert layer_[1].shape == (2, 3, 16)
                assert layer_[1].dtype == jnp.float32
            else:
                assert pay.dtype == jnp.float32
        d = stats.cache_stats()["decode"]
        assert d["cache_bytes_ring"] == 0
        assert d["cache_bytes_context"] == 3 * 2 * 3 * 2 * 16 * 16 * (
            1 if quant == "int8" else 4) + (
                3 * 2 * 3 * 16 * 4 if quant == "int8" else 0)
        short = eng.submit_decode(ids_of((5,), 21), 8)
        long = eng.submit_decode(ids_of((9,), 22), 40)    # grows to 64
        got = [np.asarray(r.result(timeout=300)) for r in (short, long)]
        assert eng._slab_dims() == (3, 64)
    finally:
        eng.stop()
        device.set_inference_quant("off")
    assert eng._slab is None        # a stopped engine holds no slab
    if quant == "off":
        assert np.array_equal(got[0], m.generate(ids_of((5,), 21)[None], 8))
        assert np.array_equal(got[1], m.generate(ids_of((9,), 22)[None], 40))
    assert m.take_step_counters() == {}


# -- (i) GPT-2's slab, positions last and donated (ISSUE 28) ----------------------
@pytest.fixture(scope="module")
def gpt2():
    from singa_tpu.models.transformer import TransformerLM

    dev = device.get_default_device()
    dev.SetRandSeed(5)
    m = TransformerLM(V, d_model=32, num_heads=2, num_layers=3, max_len=64)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32), device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


def _gpt2_stream(m, params, prompts, n_new, rung, grow_to=None, block=1):
    """Greedy streams of `prompts` through the model's own programs:
    a cohort prefill into a slab on `rung`, three single steps, the
    slab grown (where asked), the rest by single steps or blocks."""
    import jax

    B = len(prompts)
    slab = m.new_slab(params, B, rung, jax.devices()[0])
    ids = np.zeros((B, 8), np.int32)
    for r, p in enumerate(prompts):
        ids[r, :len(p)] = p
    n = np.asarray([len(p) for p in prompts], np.int32)
    lg, slab = m.prefill_slab(params, slab, put(ids), put(n),
                              put(np.arange(B, dtype=np.int32)))
    tok = np.asarray(lg).argmax(-1).astype(np.int32)
    out, pos, done = [tok], n.copy(), 1
    while done < n_new:
        if done == 4 and grow_to:
            slab = m.grow_slab(slab, grow_to)
            assert m.slab_dims(slab) == (B, grow_to)
        k = block if done >= 4 and n_new - done >= block else 1
        if k == 1:
            lg, slab = m.decode_step(params, slab, put(tok), put(pos))
            new = np.asarray(lg).argmax(-1).astype(np.int32)[None]
        else:
            new, slab = m.decode_scan(params, slab, put(tok), put(pos), k)
            new = np.asarray(new)
        out.extend(new)
        tok, pos, done = new[-1], pos + k, done + k
    return np.stack(out, 1)                                  # [B, n_new]


@pytest.mark.parametrize("block", [1, 4], ids=["step", "block"])
@pytest.mark.parametrize("quant", ["plain", "int8"])
def test_i_gpt2_slab_rows_are_generates_streams_through_growth(gpt2, quant,
                                                               block):
    """Rows of the positions-last slab, written a position at a time
    by `cache_write` and a cohort at a time by the prefill's scatter,
    grown from the 16 rung to 32 mid-stream: float32 rows decode
    `generate()`'s stream; int8 rows (whose `generate()` is float32)
    the stream of the same rows on a slab that never grew, by single
    steps."""
    m = gpt2
    prompts = [ids_of((5,), 31), ids_of((8,), 32), ids_of((3,), 33)]
    n_new = 16
    if quant == "int8":
        params = m._decode_params_quant()
        want = _gpt2_stream(m, params, prompts, n_new, 32)
    else:
        params = m._decode_params()
        want = np.stack([m.generate(p[None], n_new)[0, len(p):]
                         for p in prompts])
    got = _gpt2_stream(m, params, prompts, n_new, 16, grow_to=32,
                       block=block)
    assert np.array_equal(got, want)


@pytest.fixture(params=["gpt2", "hybrid"])
def either(request):
    return request.getfixturevalue(
        "model" if request.param == "hybrid" else request.param)


@pytest.mark.parametrize("program", ["decode_step", "decode_scan",
                                     "prefill_slab"])
def test_i_a_program_that_fails_after_it_took_the_slab(either, program,
                                                       monkeypatch):
    """Every program donates the slab. One that fails after its
    dispatch leaves nothing to retry from: the live sessions fail
    loudly (a failed prefill's cohort with them), the slab is rebuilt
    at its geometry, and queued work goes on as if nothing had been.
    (`decode_step` runs only while a live session samples, so its case
    samples; greedy sessions' single steps are `decode_scan`'s.)"""
    import jax

    m = either
    how = (dict(temperature=0.8, top_k=8, seed=3)
           if program == "decode_step" else {})
    real = getattr(m, program)
    fail = [True]

    def consumed_then_failed(params, slab, *rest):
        out = real(params, slab, *rest)
        if fail[0] and stats.cache_stats()["decode"]["tokens_streamed"]:
            fail[0] = False
            assert all(leaf.is_deleted()
                       for leaf in jax.tree_util.tree_leaves(slab))
            raise RuntimeError("the device failed under the program")
        return out

    first, late = (ids_of((5,), 51), 12), (ids_of((6,), 52), 12)
    after = (ids_of((4,), 53), 9)
    eng = serve.ServingEngine(m, max_sessions=4, max_new_tokens=24,
                              prefill_batch=2, decode_block=4,
                              max_retries=2, backoff_ms=0.1).start()
    try:
        eng.warm_decode(prompt_lens=(4, 8), max_new_tokens=24)
        stats.reset_cache_stats()
        geom = eng._slab_dims()
        monkeypatch.setattr(m, program, consumed_then_failed)
        r1 = eng.submit_decode(*first, **how)
        next(r1.tokens(timeout=60))              # live and streaming
        r2 = eng.submit_decode(*late, **how)     # its prefill may be it
        for r in (r1, r2):
            try:
                r.result(timeout=60)
            except serve.ServeDispatchError:
                pass
        assert not fail[0], "the failing program never ran"
        assert eng._slab_dims() == geom and not eng._slab_lost()
        got = np.asarray(eng.submit_decode(*after).result(timeout=60))[0]
        d = stats.cache_stats()["decode"]
    finally:
        eng.stop()
    assert d["failed"] >= 1
    assert d["sessions"] == d["completed"] + d["failed"]
    monkeypatch.undo()
    assert np.array_equal(got, _serve(m, [after])[0])


def test_i_a_failure_before_dispatch_retries_on_the_untouched_slab(either):
    """The injected `decode_fail` raises before the program is called:
    the slab is as it was, the block is retried, nobody fails and the
    streams are the undisturbed ones."""
    from singa_tpu import resilience

    m = either
    requests = [(ids_of((5,), 61), 12), (ids_of((7,), 62), 10)]
    want = _serve(m, requests)
    stats.reset_cache_stats()
    inj = resilience.FaultInjector(seed=0,
                                   schedule={"decode_fail": {2, 5}})
    got = _serve(m, requests, max_retries=2, backoff_ms=0.1,
                 fault_injector=inj)
    d = stats.cache_stats()["decode"]
    assert d["failed"] == 0 and d["completed"] == 2
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
