"""The chunk-summary / window-buffer LM against its plain reference
(ISSUE 35), at toy widths with the served configuration's mechanism
kept: d 64, 4 heads of 16, a window W of 32 positions, chunks C of 4
(8 summaries a block), 3 layers, vocabulary 320 with 8 prediction
heads' columns. Seeded float32 weights on the CPU at "highest": every
tolerance below is float32 rounding through 3 layers (7e-5 of logits
whose scale is about 10 is what is read; 2e-4 leaves that three times
its room), and no room for a summary seen a block early, a summary the
step never wrote, uniform pooling or a dropped offset, each of which a
test below shows moving the logits by 1e-2 and more. The norms' g are
drawn away from 0 here (the model draws 0), so that the unit offset of
the gain can be told.
"""
import hashlib

import numpy as np
import pytest

from perfbench.reference import evabyte_control
from perfbench.reference import evabyte_ref as ref
from singa_tpu import device, serve, stats, tensor
from singa_tpu.models.chunked_attn import ChunkedAttnLM
from singa_tpu.models.decode_lm import DecodeLM

W, C, LAYERS, V, D = 32, 4, 3, 320, 64
ARCH = dict(num_heads=4, head_dim=16, window=W, chunk=C, rope_theta=1e5,
            num_layers=LAYERS, vocab_size=V, norm_eps=1e-5)
TOL = dict(rtol=0, atol=2e-4)   # float32 rounding; the logits' scale is ~10


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
    kw = dict(d_model=D, num_heads=4, head_dim=16, window=W, chunk=C,
              num_layers=LAYERS, d_ff=96, pred_heads=8, max_len=256,
              init_std=0.3)
    kw.update(over)
    m = ChunkedAttnLM(V, **kw)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32), device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    rng = np.random.default_rng(seed)
    for name, p in m.get_states().items():
        if name.endswith(".g"):
            p.data = jnp.asarray(rng.uniform(-0.3, 0.3, p.data.shape),
                                 jnp.float32)
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


def fresh_slab(m, slots=2, seq=128):
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


def bucket_of(n):
    return 1 << (n - 1).bit_length()


def summaries_of(m, ids):
    """(sk, sv) [n, H, D] of every whole chunk of one sequence, layer
    by layer, from the reference's own forward."""
    import jax

    seen = []
    real = ref._summaries

    def spy(k, v, phi, mu, C_):
        out = real(k, v, phi, mu, C_)
        seen.append(tuple(np.asarray(t)[0] for t in out))
        return out

    ref._summaries = spy
    try:
        with jax.default_matmul_precision("highest"):
            ref.forward(states_of(m), put(np.asarray(ids)[None]),
                        ref._arch(ARCH))
    finally:
        ref._summaries = real
    return seen


# -- (a) eval forward = reference ------------------------------------------
@pytest.mark.parametrize("S", [5, W - 1, W, W + 1, 2 * W, 3 * W + 6],
                         ids=["short", "under_W", "W", "past_W",
                              "two_blocks", "blocks_and_a_partial_chunk"])
def test_a_eval_forward_equals_reference(model, S):
    ids = ids_of((2, S), seed=S)
    got = model.forward(tensor.from_numpy(ids)).to_numpy()
    assert got.shape == (2, S, V)
    np.testing.assert_allclose(got, ref_logits(model, ids), **TOL)


def _variant(model, what, ids, monkeypatch):
    """The reference's logits with one term of the mathematics changed:
    by the weights it is given or by one of its small functions swapped
    (un-jitted, so the swap is seen)."""
    import jax
    import jax.numpy as jnp

    st = states_of(model)
    if what == "uniform_pooling":
        st = {k: (0 * v if k.endswith("attn.phi") else v)
              for k, v in st.items()}
    elif what == "no_offset":
        st = {k: (0 * v if k.endswith("attn.mu") else v)
              for k, v in st.items()}
    elif what == "gain_without_unit_offset":
        monkeypatch.setattr(ref, "_rms", lambda x, g, eps: x / jnp.sqrt(
            jnp.mean(x * x, -1, keepdims=True) + eps) * g)
    elif what == "summaries_seen_in_their_own_block":
        monkeypatch.setattr(
            ref, "_remote_mask", lambda i, n, W_, C_:
            jnp.arange(n)[None, :] * C_ + C_ - 1 < i[:, None])
    elif what == "no_summaries":
        monkeypatch.setattr(
            ref, "_remote_mask", lambda i, n, W_, C_:
            jnp.zeros((len(i), n), bool))
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(st, put(ids), ref._arch(ARCH)))


@pytest.mark.parametrize("what", [
    "uniform_pooling", "no_offset", "gain_without_unit_offset",
    "summaries_seen_in_their_own_block", "no_summaries"])
def test_a_the_reference_would_notice(model, what, monkeypatch):
    """Each listed term moves the logits by far more than TOL."""
    ids = ids_of((1, 3 * W + 6))
    want = ref_logits(model, ids)
    np.testing.assert_allclose(_variant(model, "as_it_is", ids, monkeypatch),
                               want, **TOL)
    other = _variant(model, what, ids, monkeypatch)
    assert np.abs(other - want).max() > 1e-2


def test_a_what_a_query_sees_is_the_issues_index_rule():
    """Position i = W w sees exactly itself locally and (W / C) w
    summaries; a chunk of the query's own block is never seen."""
    i = np.arange(3 * W + 6)
    seen = np.asarray(ref._remote_mask(put(i), len(i) // C, W, C))
    assert (seen.sum(1) == (W // C) * (i // W)).all()
    assert not seen[2 * W - 1, (W // C):].any()      # its own block's chunks
    assert seen[2 * W, :2 * (W // C)].all()


# -- (b) prefill then decode through the slab = the full forward -----------
@pytest.mark.parametrize("P,total", [
    (5, 2 * W + 10),     # no summary at the prompt's end; two boundaries
    (W - 2, W + 12),     # n mod C != 0: the chunk closes during decode
    (W, 2 * W + 3),      # the boundary at the prompt's end
    (W + 1, W + 9),      # one position into the second block
    (2 * W, 2 * W + 5),  # two whole blocks
    (2 * W + 6, 3 * W + 9)],   # the bucket's pad tail crosses a boundary
    ids=["short", "chunk_closes_in_decode", "boundary_at_the_end",
         "one_past_it", "two_blocks", "pad_tail_crosses_a_boundary"])
def test_b_prefill_then_steps_equal_reference(model, P, total):
    m = model
    full = ids_of((total,), seed=P)
    want = ref_logits(m, full[None])[0]
    lg, slab = prefill(m, fresh_slab(m), [full[:P]], bucket_of(P))
    np.testing.assert_allclose(lg[0], want[P - 1], **TOL)
    for t in range(P, total):
        out, slab = step(m, slab, [full[t], 0], [t, 0])
        np.testing.assert_allclose(out[0], want[t], **TOL)


def test_b_prefill_writes_the_real_chunks_and_the_last_real_block(model):
    """One bucket of 128 holds prompts of 3, 41, 64 and 70 positions:
    each row reads its own last real token; its summary list holds the
    chunks complete among its real positions and zeros behind them, its
    buffer its last real block (positions 0-2, 32-40, 32-63, 64-69) and
    zeros behind it: nothing of the pad tail, whatever the bucket."""
    m = model
    lens, slots = (3, 41, 64, 70), [2, 0, 3, 1]
    rows = [ids_of((n,), seed=n) for n in lens]
    lg, slab = prefill(m, fresh_slab(m, slots=4), rows, 128, slots=slots)
    for r, (row, slot) in enumerate(zip(rows, slots)):
        n = len(row)
        np.testing.assert_allclose(lg[r], ref_logits(m, row[None])[0, -1],
                                   **TOL)
        for li, (sk, sv) in enumerate(summaries_of(m, row)):
            for name, t in (("sk", sk), ("sv", sv)):
                held = np.asarray(slab[li][name])[slot]      # [H, D, R]
                assert held.shape[-1] == 128 // C
                np.testing.assert_allclose(
                    held[..., :n // C], t.transpose(1, 2, 0), **TOL)
                assert not held[..., n // C:].any()
            last = n - W * ((n - 1) // W)
            for name in ("k", "v"):
                held = np.asarray(slab[li][name])[slot]      # [H, D, W]
                assert held[..., :last].any(-1).all()
                assert not held[..., last:].any()


@pytest.mark.parametrize("lens,bucket,ran", [
    ((33, 70), 128, 3), ((5,), 128, 1), ((W,), 64, 1), ((2 * W + 1,), 128, 3),
    ((128,), 128, 4)])
def test_b_a_buckets_pad_blocks_are_not_run(model, lens, bucket, ran):
    """Only the blocks that hold a real position of some row run: a
    bucket's blocks behind the longest row leave their hidden states
    zero (a block that ran leaves the final norm of its positions,
    which is not), and the blocks that ran, the summaries and the
    buffers are those of the same rows in a bucket of exactly those
    blocks."""
    m, params = model, model._decode_params()

    def prompts(size):
        ids = np.zeros((len(lens), size), np.int32)
        for r, n in enumerate(lens):
            ids[r, :n] = ids_of((n,), seed=n)
        return m._prompts(params, put(ids), put(np.asarray(lens, np.int32)))

    h, rows = prompts(bucket)
    h = np.asarray(h)
    assert h[:, :ran * W].any(-1).all() and not h[:, ran * W:].any()
    h_fit, rows_fit = prompts(ran * W)
    np.testing.assert_allclose(h[:, :ran * W], np.asarray(h_fit), **TOL)
    for row, fit in zip(rows, rows_fit):
        for name in ("k", "v", "sk", "sv"):
            got, want = np.asarray(row[name]), np.asarray(fit[name])
            np.testing.assert_allclose(got[..., :want.shape[-1]], want, **TOL)
            assert not got[..., want.shape[-1]:].any()


def test_b_a_pad_row_writes_nothing(model):
    m = model
    _, slab = prefill(m, fresh_slab(m), [ids_of((37,)), ids_of((3,), 1)], 64)
    before = [{n: np.asarray(a) for n, a in c.items()} for c in slab]
    _, slab = prefill(m, slab, [ids_of((5,), 2)], 8, slots=[2])  # no such slot
    for b, c in zip(before, slab):
        assert set(b) == set(c) == {"k", "v", "sk", "sv"}
        for n in b:
            assert np.array_equal(b[n], np.asarray(c[n]))


def test_b_scan_blocks_equal_single_steps(model):
    """A run-ahead block is the same greedy steps in one program,
    across a chunk's close and a block boundary: its tokens are the
    reference's argmax along the sequence it makes, its slab (buffers
    and summaries) the one k single steps leave."""
    m = model
    params = m._decode_params()
    P = W - 6
    prompt = ids_of((P,), seed=9)
    lg, slab = prefill(m, fresh_slab(m), [prompt], W)
    tok = np.array([lg[0].argmax(), 0], np.int32)
    pos = np.array([P, 0], np.int32)
    _, slab2 = prefill(m, fresh_slab(m), [prompt], W)
    seq = list(prompt) + [int(tok[0])]
    for k in (3, 8):       # 26..28, then 29..36: over the boundary at 32
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
        for n in a:
            np.testing.assert_allclose(np.asarray(a[n])[0],
                                       np.asarray(b[n])[0], **TOL)
    assert np.asarray(slab[0]["sk"])[0, ..., :(P + 11) // C].any(-1).all()
    want = ref_logits(m, np.asarray(seq)[None])[0]
    short = want.max(-1)[P:-1] - want[np.arange(P, len(seq) - 1), seq[P + 1:]]
    assert short.max() < 2e-4      # greedy by the reference too


def test_b_a_row_whose_chunk_stays_open_keeps_what_its_entry_held(model):
    """The step's second write is conditional: of two rows only the one
    whose chunk closes at this position has its summary entry changed;
    the other's whole list stays bit for bit."""
    m = model
    rows = [ids_of((C + 2,), 1), ids_of((C + 3,), 2)]
    _, slab = prefill(m, fresh_slab(m), rows, 8)
    before = [np.asarray(c["sk"]) for c in slab]
    _, slab = step(m, slab, [7, 9], [C + 2, C + 3])   # row 1 closes chunk 1
    for b, c in zip(before, slab):
        after = np.asarray(c["sk"])
        assert np.array_equal(after[0], b[0])
        assert np.array_equal(after[1, ..., :1], b[1, ..., :1])
        assert after[1, ..., 1].any() and not b[1, ..., 1].any()
    assert m.take_step_counters()["chunk_summaries_written"] == LAYERS


# -- (c) a slot's next session sees nothing of its last -----------------------
def test_c_a_slot_that_held_a_longer_session_takes_a_shorter_one(model):
    """Slot 0 holds a session 2 W + 9 positions in; a prompt of W + 3
    is then prefilled into it and decoded across the next boundary: its
    stream is the reference's, although the slot's list still holds the
    old session's summaries behind the new prompt's bucket and its
    buffer the old block's tail."""
    m = model
    old = ids_of((2 * W + 9,), seed=21)
    _, slab = prefill(m, fresh_slab(m), [old[:2 * W]], 2 * W)
    for t in range(2 * W, len(old)):
        _, slab = step(m, slab, [old[t], 0], [t, 0])
    assert np.asarray(slab[0]["sk"])[0, ..., 2 * W // C].any()
    new = ids_of((2 * W + 7,), seed=22)
    want = ref_logits(m, new[None])[0]
    P = W + 3
    lg, slab = prefill(m, slab, [new[:P]], bucket_of(P))
    np.testing.assert_allclose(lg[0], want[P - 1], **TOL)
    for t in range(P, len(new)):
        out, slab = step(m, slab, [new[t], 0], [t, 0])
        np.testing.assert_allclose(out[0], want[t], **TOL)


def _serve(m, requests, together=True, **kw):
    eng = serve.ServingEngine(m, max_sessions=2, max_new_tokens=48,
                              prefill_batch=2, decode_block=4, **kw).start()
    try:
        eng.warm_decode(prompt_lens=(3, 64), max_new_tokens=48)
        out, replies = [], []
        for p, n in requests:
            replies.append(eng.submit_decode(p, n))
            if not together:     # one at a time: each takes slot 0
                out.append(np.asarray(replies[-1].result(timeout=300))[0])
        if together:
            out = [np.asarray(r.result(timeout=300))[0] for r in replies]
        return out
    finally:
        eng.stop()


def test_c_through_the_engine_sessions_stream_what_the_reference_picks(
        model):
    """Through `ServingEngine` (`submit_decode`, the dispatcher, the
    token program and run-ahead blocks): three requests one after
    another all take slot 0; each streams what it streams beside
    another session and what the reference picks, across boundaries.
    The gauges and counters are the model's own."""
    m = model
    requests = [(ids_of((W + 9,), 11), 40), (ids_of((3,), 12), 44),
                (ids_of((2 * W - 1,), 13), 9)]
    stats.reset_cache_stats()
    in_turn = _serve(m, requests, together=False)
    d = stats.cache_stats()["decode"]
    assert d["host_leaves_per_call"] == 0
    assert d["cache_bytes_ring"] == d["cache_bytes_context"] == 0
    # 3 layers x 2 slots x (k + v) x 4 heads x 16 x W x 4 B
    assert d["cache_bytes_window"] == LAYERS * 2 * 2 * 4 * 16 * W * 4
    rung = d["cache_bytes_summary"] * C // (LAYERS * 2 * 2 * 4 * 16 * 4)
    assert rung >= 64 + 48 and rung & (rung - 1) == 0
    assert d["attn_entries_held"] == (LAYERS * 2 * (W + rung // C)
                                      * d["decode_steps"])
    assert 0 < d["attn_entries_needed"] < d["attn_entries_held"]
    assert d["attn_entries_read"] == d["attn_entries_held"]   # the einsums
    assert d["chunk_summaries_written"] > 0
    for (prompt, n), got in zip(requests, in_turn):
        assert len(got) == len(prompt) + n
        want = ref_logits(m, got[None])[0]
        at = np.arange(len(prompt) - 1, len(got) - 1)
        assert (want[at].max(-1) - want[at, got[at + 1]]).max() < 2e-4
    beside = _serve(m, requests[:2])
    for got, alone in zip(beside, in_turn):
        assert np.array_equal(got, alone)


# -- (d) growth: only the summary lists climb the ladder ----------------------
def test_d_growth_leaves_buffers_alone_and_streams_unchanged(model):
    m = model
    full = ids_of((2 * W + 12,), seed=4)
    want = ref_logits(m, full[None])[0]
    P = W + 5
    _, slab = prefill(m, fresh_slab(m, seq=64), [full[:P]], 64)
    for t in range(P, 62):
        _, slab = step(m, slab, [full[t], 0], [t, 0])
    grown = m.grow_slab(slab, 128)
    assert m.slab_dims(slab) == (2, 64) and m.slab_dims(grown) == (2, 128)
    for old, new in zip(slab, grown):
        for n in ("k", "v"):
            assert new[n] is old[n]
        for n in ("sk", "sv"):
            assert new[n].shape == (2, 4, 16, 128 // C)
            assert np.array_equal(np.asarray(new[n])[..., :64 // C],
                                  np.asarray(old[n]))
            assert not np.asarray(new[n])[..., 64 // C:].any()
    by_kind = m.slab_bytes(grown)
    assert set(by_kind) == {"window", "summary"}
    assert by_kind["window"] == m.slab_bytes(slab)["window"] \
        == LAYERS * 2 * 2 * 4 * 16 * W * 4
    assert by_kind["summary"] == 2 * m.slab_bytes(slab)["summary"]
    assert m._slab_sig(grown) != m._slab_sig(slab)
    assert m._slab_extra(grown)[0][2] == [2, 4, 16, 128 // C]
    slab = grown
    for t in range(62, len(full)):       # over the boundary at 64
        out, slab = step(m, slab, [full[t], 0], [t, 0])
        np.testing.assert_allclose(out[0], want[t], **TOL)


# -- (e) the planted faults fail the comparison -------------------------------
@pytest.mark.parametrize("fault", evabyte_control.FAULTS)
def test_e_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    """The control's two faults, planted as it plants them: a stream
    decoded across a chunk's close and the next boundary leaves the
    reference by far more than TOL (without the fault: the tests
    above)."""
    for name in ("_seen_summaries", "_slot_step"):   # put back afterwards
        monkeypatch.setattr(ChunkedAttnLM, name, getattr(ChunkedAttnLM, name))
    evabyte_control.plant(fault)
    m = build()
    full = ids_of((2 * W + 8,), seed=31)
    want = ref_logits(m, full[None])[0]
    P = W + 6
    lg, slab = prefill(m, fresh_slab(m), [full[:P]], 64)
    np.testing.assert_allclose(lg[0], want[P - 1], **TOL)
    worst = 0.0
    for t in range(P, len(full)):
        out, slab = step(m, slab, [full[t], 0], [t, 0])
        worst = max(worst, np.abs(out[0] - want[t]).max())
    assert worst > 1e-2


# -- (f) the counters' arithmetic ----------------------------------------------
def test_f_the_four_counters_over_a_whole_window(model):
    """Two rows decode a whole window of W positions each from
    different phases: a row's chunk closes every C-th step, so
    chunk_summaries_written / tokens / layers = 1 / C exactly; the
    entries needed are (p mod W + 1) + (W / C)(p // W) a row-layer, the
    entries held rows x layers x (W + rung / C) a step; at these widths
    (a window of 32: no whole block of 128) the two `einsum`s read
    every buffer and every list whole, so the entries read are the
    entries held."""
    m = model
    starts = np.array([W + 3, 5], np.int32)
    rows = [ids_of((n,), seed=n) for n in starts]
    _, slab = prefill(m, fresh_slab(m), rows, 64)
    m.take_step_counters()
    total = dict.fromkeys(m.step_counter_names, 0)
    need = 0
    for s in range(W):
        pos = starts + s
        _, slab = step(m, slab, [1, 2], pos)
        for name, v in m.take_step_counters().items():
            total[name] += v
        need += LAYERS * int(((pos % W) + 1 + (W // C) * (pos // W)).sum())
    assert total["chunk_summaries_written"] * C == 2 * W * LAYERS
    assert total["attn_entries_needed"] == need
    assert total["attn_entries_held"] == W * 2 * LAYERS * (W + 128 // C)
    assert total["attn_entries_read"] == total["attn_entries_held"]
    # a block's counters are its steps' sums
    m.decode_scan(m._decode_params(), slab, put(np.array([1, 2], np.int32)),
                  put(starts + W), 4)
    block = m.take_step_counters()
    assert block["attn_entries_held"] == 4 * 2 * LAYERS * (W + 128 // C)
    assert block["attn_entries_read"] == block["attn_entries_held"]
    assert block["chunk_summaries_written"] == 2 * LAYERS


# -- (g) what the model is, and what the base's split left alone --------------
def test_g_what_is_not_implemented_says_so_by_mechanism(model):
    m = model
    for call, words in (
            (lambda: m.train_one_batch(None, None), "no training path"),
            (lambda: m._decode_params_quant(), "window buffers and chunk"),
            (lambda: m.export_slab_rows(None, 0, 1), "KV export"),
            (lambda: m.import_slab_rows(None, 0, None), "replays its ledger"),
            (lambda: m._shard_decode_params(None, None), "no sharding rule")):
        with pytest.raises(NotImplementedError, match=words):
            call()
    with pytest.raises(NotImplementedError, match="holds whole layers"):
        m.compile([], mesh=object())
    assert m.scan_unroll == 1 and not hasattr(m, "held")
    assert not hasattr(m, "dense_rows") and not hasattr(m, "_experts")
    assert m._trace_key() == DecodeLM._trace_key(m)
    phi = np.asarray(m.blocks.l0.attn.phi.data)
    assert np.abs(phi).max() <= 16 ** -0.5 and np.unique(phi).size > 16


ROUTED = {   # read on the parent of PR 35 (commit 45f45f7), same toys
    "hybrid": (29, "6f9c5519a046bcc6a3f70b676d7d66d31a8a3558",
               "4f9470124a57"),
    "shortconv": (31, "1fcf76865465d5f6d4a1867d5cc308cd535a04f4",
                  "799bf0357b73")}


@pytest.mark.parametrize("which", sorted(ROUTED))
def test_g_the_base_split_left_the_routed_models_as_they_were(which):
    """`DrawnDecodeLM` lost what only the routed models use to
    `RoutedDrawnLM`: their parameter names, the values drawn from a
    seed, `_trace_key`, `step_counter_names` and `scan_unroll` are the
    parent commit's."""
    from singa_tpu.models.drawn_lm import DrawnDecodeLM, RoutedDrawnLM
    from singa_tpu.models.hybrid_moe import HybridWindowMoELM
    from singa_tpu.models.shortconv_moe import ShortConvMoELM

    if which == "hybrid":
        m = HybridWindowMoELM(
            64, d_model=32, num_heads=4, head_dim=12, v_head_dim=8,
            kv_heads_full=1, kv_heads_window=2, window=4, rotary_dim=4,
            layer_pattern=(0, 1, 0), moe_layers=(0, 1, 1), d_ff=64,
            d_ff_expert=16, n_experts=8, experts_per_token=2, held=(2, 4),
            max_len=64, init_std=0.3)
    else:
        m = ShortConvMoELM(
            64, d_model=48, num_heads=4, kv_heads=2, head_dim=12,
            layer_types=("conv", "full_attention", "conv"),
            num_dense_layers=1, d_ff=64, d_ff_expert=16, n_experts=8,
            experts_per_token=2, held=(0, 8), max_len=64, init_std=0.3)
    dev = device.get_default_device()
    dev.SetRandSeed(5)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32), device=dev)],
              is_train=False, use_graph=False)
    names = sorted(m.get_states())
    count, digest, drawn = ROUTED[which]
    assert len(names) == count
    assert hashlib.sha1("\n".join(names).encode()).hexdigest() == digest
    assert hashlib.sha1(np.asarray(
        m.get_states()[names[3]].data, np.float32).tobytes()
    ).hexdigest()[:12] == drawn
    assert isinstance(m, RoutedDrawnLM) and isinstance(m, DrawnDecodeLM)
    assert m._trace_key() == DecodeLM._trace_key(m) + (256,)
    assert m.step_counter_names == (
        "moe_assignments_local", "moe_experts_touched", "moe_expert_load_max")
    assert m.scan_unroll is True
    assert not issubclass(ChunkedAttnLM, RoutedDrawnLM)
    assert ChunkedAttnLM.step_counter_names == (
        "attn_entries_needed", "attn_entries_held", "chunk_summaries_written",
        "attn_entries_read")


@pytest.mark.parametrize("L,R,seen", [
    (512, 256, 0), (512, 256, 100), (512, 256, 128), (512, 256, 256),
    (768, 128, 128), (32, 24, 16), (5, 1, 0)])
def test_h_block_attend_is_one_softmax_over_the_block_and_the_seen_summaries(
        L, R, seen):
    """The prefill's kernel against the plain formula, past what the toy
    model reaches (its block is one tile of queries): several tiles of
    256 queries (whole tiles of keys behind the causal one), a list of
    several tiles of summaries of which `seen` is none, a part of a
    tile, a whole tile, or all."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.ops.pallas_kernels import block_attend

    rng = np.random.default_rng(L + R + seen)
    B, H, Dh = 2, 3, 16
    q, k, v = (put(rng.standard_normal((B, H, L, Dh), np.float32))
               for _ in range(3))
    sk, sv = (put(rng.standard_normal((B, H, R, Dh), np.float32))
              for _ in range(2))
    got = jax.jit(block_attend)(q, k, v, sk, sv, jnp.int32(seen))
    s = jnp.concatenate([jnp.einsum("bhqd,bhkd->bhqk", q, k),
                         jnp.einsum("bhqd,bhrd->bhqr", q, sk)], -1) / 4.0
    mask = jnp.concatenate([
        jnp.arange(L)[None, :] <= jnp.arange(L)[:, None],
        jnp.broadcast_to(jnp.arange(R)[None, :] < seen, (L, R))], -1)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    want = jnp.einsum("bhqk,bhkd->bhqd", p, jnp.concatenate([v, sv], 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5)


# -- (i) the decode step's length-aware attention (ISSUE 36) -----------------
KW, KLAYERS = 256, 2     # a window of two 128-entry blocks: the kernel's path


def _plain_attend(q, k, v, sk, sv, at, seen, round_to=None):
    """The formula `window_summary_attend` stands for, every buffer and
    every list read whole: one softmax over a row's buffer entries
    0..at and its first `seen` summaries, float32 at "highest"; the
    probabilities rounded to `round_to` (the values' dtype) before
    they weigh the values."""
    import jax
    import jax.numpy as jnp

    Wk, R = k.shape[3], sk.shape[3]
    f32 = [t.astype(jnp.float32) for t in (q, k, v, sk, sv)]
    q, k, v, sk, sv = f32
    s = jnp.concatenate([jnp.einsum("bhd,bhdt->bht", q, t,
                                    precision="highest")
                         for t in (k, sk)], -1) / np.sqrt(q.shape[-1])
    mask = jnp.concatenate([jnp.arange(Wk)[None, :] <= at[:, None],
                            jnp.arange(R)[None, :] < seen[:, None]], -1)
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), -1)
    if round_to is not None:
        p = p.astype(round_to).astype(jnp.float32)
    return jnp.einsum("bht,bhdt->bhd", p, jnp.concatenate([v, sv], -1),
                      precision="highest")


def _kernel_alone(R, dtype):
    """The kernel against the plain formula over buffers and lists full
    of random entries, so whatever lies past `at` or `seen` (a block's
    tail; the blocks a row no longer holds: what the last block, or the
    slot's last session, left there) would show: rows at `at` = 0, 127,
    128 and W - 1 and one inside each block, `seen` = 0, 128, all R,
    and two that are no multiple of 128 (what the planted
    `summaries_seen_early` asks for)."""
    from singa_tpu.ops.pallas_kernels import window_summary_attend

    rng = np.random.default_rng(R)
    at = np.array([0, 127, 128, KW - 1, 5, 200], np.int32)
    seen = np.array([0, 128, R, 37, R - 1, 129 if R > 129 else 1], np.int32)
    B, H, Dh = len(at), 4, 16
    q = put(rng.standard_normal((B, H, Dh), np.float32)).astype(dtype)
    k, v = (put(rng.standard_normal((B, H, Dh, KW), np.float32)).astype(dtype)
            for _ in range(2))
    sk, sv = (put(rng.standard_normal((B, H, Dh, R), np.float32)
                  ).astype(dtype) for _ in range(2))
    got = np.asarray(window_summary_attend(q, k, v, sk, sv, put(at),
                                           put(seen)))
    assert got.shape == (B, H, Dh) and got.dtype == np.float32
    want = _plain_attend(q, k, v, sk, sv, put(at), put(seen), round_to=dtype)
    # a probability on the edge of two bfloat16 values may round either way
    np.testing.assert_allclose(got, np.asarray(want), rtol=0,
                               atol=2e-6 if dtype == np.float32 else 2e-5)
    if dtype != np.float32:
        # the rounding of the probabilities is as stated: left out, the
        # formula lies a hundred times further from the kernel
        exact = _plain_attend(q, k, v, sk, sv, put(at), put(seen))
        assert np.abs(got - np.asarray(exact)).max() > 2e-4
    # lengths past the arrays are clamped to them
    wild = np.asarray(window_summary_attend(
        q, k, v, sk, sv, put(at + KW * (at == KW - 1)),
        put(seen + 5 * R * (seen == R))))
    assert np.array_equal(wild, got)


def _einsum_twin(monkeypatch, **kw):
    """The same weights behind the two `einsum`s: the shape rule,
    answered "no blocks" while this model's programs are made (a test's
    own steering: the program has no option for it)."""
    from singa_tpu.ops import pallas_kernels

    twin = build(**kw)
    real = twin._slot_step

    def slot_step(*a):
        with monkeypatch.context() as mp:
            mp.setattr(pallas_kernels, "window_summary_blocks",
                       lambda W_, R_: (0, 0))
            return real(*a)

    twin._slot_step = slot_step
    return twin


def _stream(chunk, rung, P, fault, monkeypatch):
    """A stream decoded through the kernel's path (window 256; the
    list's `rung / chunk` entries are one, two or four blocks) from a
    prompt of P positions that ends in the second half of its block,
    across the next boundary (there `at` = 0 with the whole of the last
    block still in the buffer behind it, and the row sees W / chunk
    summaries more: 128, or 64 or 16, no whole block) to the
    reference's logits and to the `einsum` path's, step by step; the
    counters say the kernel ran (read < held). With a planted fault
    (`summaries_seen_early`: `seen` = pos // chunk, any number;
    `summary_unwritten`: the chunks that close in decoding stay zero)
    the kernel serves the fault as planted: the `einsum` path's logits
    with the same fault, far from the reference."""
    kw = dict(window=KW, chunk=chunk, num_layers=KLAYERS, max_len=rung)
    for name in ("_seen_summaries", "_slot_step"):   # put back afterwards
        monkeypatch.setattr(ChunkedAttnLM, name, getattr(ChunkedAttnLM, name))
    if fault:
        evabyte_control.plant(fault)
    m, twin = build(**kw), _einsum_twin(monkeypatch, **kw)
    total = KW * (P // KW + 1) + 6
    full = ids_of((total,), seed=chunk + rung)
    want = ref_logits(m, full[None], window=KW, chunk=chunk,
                      num_layers=KLAYERS)[0]
    lg, slab = prefill(m, fresh_slab(m, seq=rung), [full[:P]], bucket_of(P))
    _, slab2 = prefill(twin, fresh_slab(twin, seq=rung), [full[:P]],
                       bucket_of(P))
    np.testing.assert_allclose(lg[0], want[P - 1], **TOL)
    m.take_step_counters()
    worst = 0.0
    for t in range(P, total):
        out, slab = step(m, slab, [full[t], 0], [t, 0])
        plain, slab2 = step(twin, slab2, [full[t], 0], [t, 0])
        np.testing.assert_allclose(out, plain, **TOL)
        worst = max(worst, np.abs(out[0] - want[t]).max())
        n = m.take_step_counters()
        assert n["attn_entries_needed"] <= n["attn_entries_read"] \
            < n["attn_entries_held"] == KLAYERS * 2 * (KW + rung // chunk)
        assert twin.take_step_counters()["attn_entries_read"] \
            == n["attn_entries_held"]
    assert (worst > 1e-2) if fault else (worst < TOL["atol"])
    for a, b in zip(slab, slab2):
        for name in a:
            np.testing.assert_allclose(np.asarray(a[name]),
                                       np.asarray(b[name]), **TOL)


def _reused_slot(monkeypatch):
    """Slot 0 holds a session 2 W + 130 positions in (its buffer's
    second block and its list's second block written); a prompt of
    W + 3 positions is then prefilled into it and decoded: the stream
    is the reference's although the buffer's second block and the list
    behind the new prompt's bucket still hold the old session's
    entries, which the kernel never fetches."""
    m = build(window=KW, chunk=2, num_layers=KLAYERS, max_len=1024)
    over = dict(window=KW, chunk=2, num_layers=KLAYERS)
    old = ids_of((2 * KW + 130,), seed=41)
    _, slab = prefill(m, fresh_slab(m, seq=1024), [old[:2 * KW]], 512)
    for t in range(2 * KW, len(old)):
        _, slab = step(m, slab, [old[t], 0], [t, 0])
    assert np.asarray(slab[0]["sk"])[0, ..., 2 * KW // 2].any()
    assert np.asarray(slab[0]["k"])[0, ..., 129].any()
    new = ids_of((KW + 12,), seed=42)
    want = ref_logits(m, new[None], **over)[0]
    P = KW + 3
    lg, slab = prefill(m, slab, [new[:P]], 512)
    np.testing.assert_allclose(lg[0], want[P - 1], **TOL)
    for t in range(P, len(new)):
        out, slab = step(m, slab, [new[t], 0], [t, 0])
        np.testing.assert_allclose(out[0], want[t], **TOL)


def _block_of_8(monkeypatch):
    """A `slot_scan_8` block through the kernel is eight single steps,
    over the buffer's block boundary at 128 (positions W + 124 .. W +
    131): the same tokens, the same slab, the counters their sums."""
    m = build(window=KW, chunk=2, num_layers=KLAYERS, max_len=512)
    params = m._decode_params()
    P = KW + 124
    prompt = ids_of((P,), seed=51)
    lg, slab = prefill(m, fresh_slab(m, seq=512), [prompt], 512)
    _, slab2 = prefill(m, fresh_slab(m, seq=512), [prompt], 512)
    tok = np.array([lg[0].argmax(), 0], np.int32)
    pos = np.array([P, 0], np.int32)
    m.take_step_counters()
    toks, slab = m.decode_scan(params, slab, put(tok), put(pos), 8)
    block = m.take_step_counters()
    toks, t1 = np.asarray(toks), tok.copy()
    total = dict.fromkeys(m.step_counter_names, 0)
    for s in range(8):
        out, slab2 = step(m, slab2, t1, pos + s)
        t1 = out.argmax(-1).astype(np.int32)
        assert t1[0] == toks[s, 0]
        for name, n in m.take_step_counters().items():
            total[name] += n
    assert block == total
    assert block["attn_entries_needed"] < block["attn_entries_read"] \
        < block["attn_entries_held"]
    for a, b in zip(slab, slab2):
        for name in a:
            np.testing.assert_allclose(np.asarray(a[name])[0],
                                       np.asarray(b[name])[0], **TOL)


def _read_over_a_window(monkeypatch):
    """`attn_entries_read` over a whole window of 256 steps of two rows
    in different phases, from the step's own program (only its counters
    are asked for, so nothing else of it runs): 128 x (at // 128 + 1)
    of the buffer and 128 x ceil(seen / 128) of the list a row-layer,
    never under the need, never over what is held; with `seen` as the
    served model has it (a multiple of 128 here: W / C = 128) the
    list's read is exact to the entry."""
    import jax

    m = build(window=KW, chunk=2, num_layers=KLAYERS, max_len=1024)
    params, slab = m._decode_params(), fresh_slab(m, seq=1024)
    count = jax.jit(lambda tok, pos: m._slot_step(params, slab, tok, pos)[2])
    starts = np.array([KW + 3, 2 * KW + 200], np.int32)
    names = list(m.step_counter_names)
    for s in range(KW):
        pos = starts + s
        n = dict(zip(names, np.asarray(count(put(pos * 0), put(pos)))))
        at, seen = pos % KW, (KW // 2) * (pos // KW)
        assert n["attn_entries_read"] == KLAYERS * int(
            (128 * (at // 128 + 1) + seen).sum())
        assert n["attn_entries_needed"] == KLAYERS * int(
            (at + 1 + seen).sum())
        assert n["attn_entries_held"] == KLAYERS * 2 * (KW + 1024 // 2)
        assert n["attn_entries_needed"] <= n["attn_entries_read"] \
            < n["attn_entries_held"]


def _the_shapes_alone_decide(monkeypatch):
    """The rule, and that nothing else chooses: whole 128-entry blocks
    of buffer and list, two or more of the buffer."""
    from singa_tpu.ops.pallas_kernels import (window_summary_attend,
                                              window_summary_blocks)

    assert window_summary_blocks(2048, 1024) == (16, 8)
    assert window_summary_blocks(2048, 128) == (16, 1)
    assert window_summary_blocks(256, 128) == (2, 1)
    for W_, R_ in ((128, 128), (8, 16), (32, 32), (256, 64), (256, 1),
                   (384, 192), (200, 128)):
        assert window_summary_blocks(W_, R_) == (0, 0)
    z = np.zeros((1, 2, 8, 32), np.float32)
    with pytest.raises(ValueError, match="do not divide into blocks"):
        window_summary_attend(z[..., 0], z, z, z, z, np.zeros(1, np.int32),
                              np.zeros(1, np.int32))


KERNEL_CASES = {
    "alone_float32_list_of_128": lambda mp: _kernel_alone(128, np.float32),
    "alone_float32_list_of_256": lambda mp: _kernel_alone(256, np.float32),
    "alone_bfloat16_probabilities_rounded":
        lambda mp: _kernel_alone(256, "bfloat16"),
    "stream_chunk_2_list_of_256": lambda mp: _stream(2, 512, 140, None, mp),
    "stream_chunk_2_list_of_512_two_boundaries_in":
        lambda mp: _stream(2, 1024, KW + 140, None, mp),
    "stream_chunk_4_list_of_128": lambda mp: _stream(4, 512, 140, None, mp),
    "stream_chunk_16_list_of_128":
        lambda mp: _stream(16, 2048, 140, None, mp),
    "stream_summaries_seen_early_as_planted":
        lambda mp: _stream(2, 512, 140, "summaries_seen_early", mp),
    "stream_summary_unwritten_as_planted":
        lambda mp: _stream(2, 512, 140, "summary_unwritten", mp),
    "slot_that_held_a_longer_session": _reused_slot,
    "block_of_8_equals_eight_steps": _block_of_8,
    "entries_read_over_a_whole_window": _read_over_a_window,
    "the_shapes_alone_decide": _the_shapes_alone_decide,
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_i_the_decode_step_reads_only_the_blocks_a_row_holds(case,
                                                             monkeypatch):
    """`window_summary_attend` (interpreted here; compiled by Mosaic at
    the cell's widths in `tests/test_tpu_compile_widths.py`) alone
    against the plain formula, and behind `ChunkedAttnLM._slot_step` at
    widths that take it against the `einsum` path and against
    `perfbench/reference/evabyte_ref`, float32 at "highest" to TOL:
    each case's own words say what it holds."""
    KERNEL_CASES[case](monkeypatch)
