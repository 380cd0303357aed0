"""Chained decode dispatch: while nothing can join or leave at a block's
end, `ServingEngine` dispatches the next block behind the one in flight
before reading that one back.

What it must keep, at toy widths on the CPU:
  - the streams: chained and unchained engines serve bit-identical
    greedy streams, for every decode-tier family, and a block keeps its
    own counters when another is dispatched behind it;
  - the rule: `decode_steps_chained` rises in a full pool and stays 0
    when a session ends at the block's end, a slot is free, a session
    samples, or a session has a deadline;
  - faults: a failure or a hang due at a chained dispatch falls back to
    the unchained dispatch and its retry; no stream is torn or
    duplicated;
  - the drain: `stop(drain=False)` and `export_decode_sessions` with a
    block in flight hand out every dispatched block first, so the slab
    and the ledger agree (the exported rows resume to the same tokens).
"""
import threading

import numpy as np
import pytest

from singa_tpu import device, resilience, serve, stats, tensor
from singa_tpu.models.block_sparse_moe import BlockSparseMoELM
from singa_tpu.models.chunked_attn import ChunkedAttnLM
from singa_tpu.models.hybrid_moe import HybridWindowMoELM
from singa_tpu.models.shortconv_moe import ShortConvMoELM
from singa_tpu.models.transformer import TransformerLM

V = 64
MAXLEN = 64
NEW = 24


@pytest.fixture(autouse=True)
def _highest():
    before = tensor.get_matmul_precision()
    tensor.set_matmul_precision("highest")
    yield
    tensor.set_matmul_precision(before)


def _build(family):
    """A toy of each decode-tier family, eval-compiled."""
    dev = device.get_default_device()
    dev.SetRandSeed(11)
    if family == "transformer":
        m = TransformerLM(V, d_model=32, num_heads=2, num_layers=2,
                          max_len=MAXLEN)
    elif family == "hybrid":
        m = HybridWindowMoELM(
            V, d_model=32, num_heads=4, head_dim=12, v_head_dim=8,
            kv_heads_full=1, kv_heads_window=2, window=4, rotary_dim=4,
            layer_pattern=(0, 1, 0), moe_layers=(0, 1, 1), d_ff=64,
            d_ff_expert=16, n_experts=8, experts_per_token=2, held=(2, 4),
            max_len=MAXLEN, init_std=0.3)
    elif family == "shortconv":
        m = ShortConvMoELM(
            V, d_model=48, num_heads=4, kv_heads=2, head_dim=12,
            layer_types=("conv", "full_attention", "conv"),
            num_dense_layers=1, d_ff=64, d_ff_expert=16, n_experts=8,
            experts_per_token=2, held=(0, 8), max_len=MAXLEN, init_std=0.3)
    elif family == "chunked":
        m = ChunkedAttnLM(
            V, d_model=48, num_heads=4, head_dim=12, window=8, chunk=2,
            num_layers=2, d_ff=64, pred_heads=2, max_len=MAXLEN,
            init_std=0.3)
    else:
        m = BlockSparseMoELM(
            V, d_model=48, num_heads=4, kv_heads=2, head_dim=12,
            rotary_dim=4, index_heads=2, index_dim=12, block=4,
            top_blocks=1, local_blocks=1, moe_layers=(0, 1), d_ff=64,
            d_ff_expert=16, d_ff_shared=16, n_experts=8,
            experts_per_token=2, held=(2, 4), max_len=MAXLEN,
            prefill_block=8, prefill_tile=4, init_std=0.3)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32), device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


@pytest.fixture(scope="module")
def lm():
    return _build("transformer")


def ids_of(n, seed):
    return np.random.default_rng(seed).integers(0, V, (1, n),
                                                dtype=np.int32)


def _engine(m, **kw):
    cfg = dict(max_sessions=2, max_new_tokens=NEW, prefill_batch=2,
               decode_block=4)
    cfg.update(kw)
    eng = serve.ServingEngine(m, **cfg).start()
    eng.warm_decode(prompt_lens=(3, 7), max_new_tokens=NEW)
    return eng


def _unchained(eng, monkeypatch):
    """The same engine with the rule held false: every block is read
    back before the next is dispatched."""
    monkeypatch.setattr(eng, "_decode_chains", lambda live, ahead: False)
    return eng


def _closed_loop(eng, requests):
    """Submit in turn, coming back after a shed (a full pool); the
    streams as arrays, in order."""
    replies = []
    for p, n in requests:
        while True:
            try:
                replies.append(eng.submit_decode(p, n))
                break
            except serve.ServeOverloadError:
                threading.Event().wait(0.002)
    return [np.asarray(r.result(timeout=300))[0] for r in replies]


def _delta(fn):
    d0 = stats.decode_stats().snapshot()
    out = fn()
    d1 = stats.decode_stats().snapshot()
    return out, {k: d1[k] - d0[k] for k in d1
                 if isinstance(d1.get(k), (int, float))}


REQUESTS = [(ids_of(5, 1), 13), (ids_of(3, 2), 10), (ids_of(7, 3), 12),
            (ids_of(4, 4), 9)]


@pytest.mark.parametrize("family", ["transformer", "hybrid", "shortconv",
                                    "chunked", "blocksparse"])
def test_chained_and_unchained_engines_serve_the_same_streams(
        family, monkeypatch):
    """Four greedy sessions through a pool of two, so each runs beside
    another most of its life: the chained engine dispatches blocks
    behind blocks in flight, compiles nothing after `warm_decode` for
    it, and serves what the unchained engine serves, bit for bit:
    `generate()`'s streams where the model has it."""
    m = _build(family)
    eng = _engine(m)
    try:
        traced = stats.cache_stats()["decode"]["retraces"]
        chained, dc = _delta(lambda: _closed_loop(eng, REQUESTS))
        assert stats.cache_stats()["decode"]["retraces"] == traced
        _unchained(eng, monkeypatch)
        plain, dp = _delta(lambda: _closed_loop(eng, REQUESTS))
    finally:
        eng.stop()
    assert dc["decode_steps_chained"] > 0
    assert dp["decode_steps_chained"] == 0
    for a, b in zip(chained, plain):
        assert np.array_equal(a, b)
    assert dc["tokens_streamed"] == dp["tokens_streamed"]
    if hasattr(m, "generate"):
        for (p, n), got in zip(REQUESTS, chained):
            assert np.array_equal(got, m.generate(p, n)[0])


def test_a_block_keeps_its_own_counters_and_hands_on_its_last_token():
    """Two blocks dispatched back to back, the second from the first's
    carry before anything is read: each block's counters, detached at
    its dispatch, are what that block counts alone, and the carry is
    the first block's last token row."""
    import jax

    m = _build("chunked")
    params = m._decode_params()
    put = device.get_default_device().put
    ids = np.zeros((2, 8), np.int32)
    ids[0, :5], ids[1, :3] = ids_of(5, 1)[0], ids_of(3, 2)[0]
    slab = m.new_slab(params, 2, 16, jax.devices()[0])
    lg, slab = m.prefill_slab(params, slab, put(ids),
                              put(np.asarray([5, 3], np.int32)),
                              put(np.asarray([0, 1], np.int32)))
    tok = np.asarray(lg).argmax(-1).astype(np.int32)
    pos = np.asarray([5, 3], np.int32)

    def alone(slab, t, p):
        toks, slab = m.decode_scan(params, slab, put(t), put(p), 2)
        return np.asarray(toks), slab, m.take_step_counters()

    twin = jax.tree_util.tree_map(lambda a: put(np.asarray(a)), slab)
    want1, twin, c1 = alone(twin, tok, pos)
    want2, twin, c2 = alone(twin, want1[-1], pos + 2)
    first, slab = m.decode_scan(params, slab, put(tok), put(pos), 2)
    carry, vec1 = m.take_next_tokens(), m.detach_step_counters()
    second, slab = m.decode_scan(params, slab, carry, put(pos + 2), 2)
    vec2 = m.detach_step_counters()
    assert np.array_equal(np.asarray(carry), want1[-1])
    assert np.array_equal(np.asarray(first), want1)
    assert np.array_equal(np.asarray(second), want2)
    assert m.take_step_counters(vec1) == c1
    assert m.take_step_counters(vec2) == c2
    assert c1["attn_entries_needed"] != c2["attn_entries_needed"]
    assert m.take_step_counters() == {}


@pytest.mark.parametrize("case", ["full_pool", "ends_at_block_end",
                                  "slot_free", "samples", "deadline"])
def test_steps_chained_rise_in_a_full_pool_and_only_there(lm, case):
    """One session: in a pool of one, with nothing to stop it, most of
    its steps go behind a block in flight; none do when its budget ends
    at the first block's end, beside a free slot, while it samples, or
    under a deadline. Each stream is `generate()`'s."""
    p = ids_of(5, 7)
    n, kw, slots = 17, {}, 1
    if case == "ends_at_block_end":
        n = 5           # the first token from the prefill, then 4: one block
    elif case == "slot_free":
        slots = 2
    elif case == "samples":
        kw = dict(temperature=0.8, top_k=8, seed=3)
    elif case == "deadline":
        kw = dict(deadline_ms=600_000)
    eng = _engine(lm, max_sessions=slots)
    try:
        got, d = _delta(lambda: np.asarray(
            eng.submit_decode(p, n, **kw).result(timeout=300))[0])
    finally:
        eng.stop()
    gen = {k: v for k, v in kw.items() if k != "deadline_ms"}
    assert np.array_equal(got, lm.generate(p, n, **gen)[0])
    assert d["decode_steps"] == n - 1
    if case == "full_pool":
        # four blocks of 4: the last three behind another
        assert d["decode_steps_chained"] == n - 1 - 4
    else:
        assert d["decode_steps_chained"] == 0


def _dispatches(eng, monkeypatch, on_chained=None):
    """Record each enqueue as (ordinal, steps, chained) and call
    `on_chained` after the first chained one is in flight."""
    seen, real = [], eng._decode_enqueue

    def enqueue(params, tok, pos, k, sampled, t0):
        blk = real(params, tok, pos, k, sampled, t0)
        chained = not isinstance(tok, np.ndarray)
        seen.append((blk.idx, k, chained))
        if chained and on_chained is not None and \
                sum(c for *_, c in seen) == 1:
            on_chained()
        return blk

    monkeypatch.setattr(eng, "_decode_enqueue", enqueue)
    return seen


@pytest.mark.parametrize("fault", ["decode_fail", "decode_hang",
                                   "decode_fail_no_retry"])
def test_a_fault_due_at_a_chained_dispatch_falls_back(lm, fault,
                                                      monkeypatch):
    """A clean run finds the ordinals its chained blocks took; a fault
    at the second of them is met by the unchained dispatch at that
    ordinal instead (no block is chained there), with its retry: the
    stream is the clean one. With no retry left the session fails
    loudly, and what it streamed is a prefix of the clean stream,
    nothing torn, nothing twice."""
    p, n = ids_of(5, 7), 17
    eng = _engine(lm, max_sessions=1, decode_block=2)
    try:
        seen = _dispatches(eng, monkeypatch)
        clean = np.asarray(eng.submit_decode(p, n).result(timeout=300))[0]
    finally:
        eng.stop()
    chained = [idx for idx, _, c in seen if c]
    assert len(chained) >= 2
    at = chained[1]
    kind = fault.replace("_no_retry", "")
    inj = resilience.FaultInjector(seed=0, schedule={kind: {at}},
                                   hang_s=0.01)
    retries = 0 if fault.endswith("no_retry") else 1
    eng = _engine(lm, max_sessions=1, decode_block=2, fault_injector=inj,
                  max_retries=retries, backoff_ms=0.1)
    try:
        seen = _dispatches(eng, monkeypatch)
        reply = eng.submit_decode(p, n)
        streamed, err = [], None
        try:
            for tok in reply.tokens(timeout=300):
                streamed.append(tok)
        except serve.ServeDispatchError as e:
            err = e
    finally:
        eng.stop()
    # the failed attempt never dispatched; the hung one did, unchained
    assert [c for idx, _, c in seen if idx == at] == (
        [False] if fault == "decode_hang" else [])
    assert streamed == list(clean[p.shape[1]:][:len(streamed)])
    if fault == "decode_fail_no_retry":
        assert err is not None and len(streamed) < n
    else:
        assert err is None
        assert np.array_equal(np.asarray(reply.result(timeout=1))[0], clean)


@pytest.mark.parametrize("how", ["export", "stop"])
def test_a_block_in_flight_is_handed_out_before_the_loop_returns(
        lm, how, monkeypatch):
    """`export_decode_sessions` / `stop(drain=False)` arrive while a
    block is in flight behind another: the loop reads back and hands
    out both before it returns, so the ledger holds every dispatched
    step's token and the exported rows are the slab's at that point:
    resumed elsewhere, the session finishes with `generate()`'s
    tokens."""
    p, n = ids_of(5, 7), 21
    want = lm.generate(p, n)[0]
    eng = _engine(lm, max_sessions=1, decode_block=2)
    arrived, go = threading.Event(), threading.Event()

    def hold():         # the dispatcher, with two blocks in flight
        arrived.set()
        go.wait(30)

    seen = _dispatches(eng, monkeypatch, on_chained=hold)
    out = {}
    try:
        reply = eng.submit_decode(p, n)
        assert arrived.wait(60)
        act = threading.Thread(target=lambda: out.update(
            ckpts=eng.export_decode_sessions() if how == "export"
            else eng.stop(drain=False)))
        act.start()
        threading.Event().wait(0.1)  # export / stop waits on the loop
        go.set()
        act.join(60)
    finally:
        go.set()
        eng.stop()
    dispatched = 1 + sum(k for _, k, _ in seen)
    assert dispatched < n and sum(c for *_, c in seen) == 1
    streamed = []
    with pytest.raises((serve.ServeMigratedError, serve.ServeClosedError)):
        for tok in reply.tokens(timeout=60):
            streamed.append(tok)
    assert streamed == list(want[p.shape[1]:][:dispatched])
    if how == "stop":
        return
    (ckpt,) = out["ckpts"]
    assert len(ckpt["toks"]) == dispatched
    assert ckpt["kv"].shape[3] == p.shape[1] + dispatched - 1
    monkeypatch.undo()
    other = _engine(lm, max_sessions=1, decode_block=2)
    try:
        resumed = np.asarray(other.resume_decode(ckpt).result(timeout=300))
    finally:
        other.stop()
    assert np.array_equal(resumed[0], want)
