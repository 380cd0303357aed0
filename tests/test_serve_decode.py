"""Token-granularity continuous batching: the KV-cached decode tier
(ISSUE 16).

Acceptance pins:
  - sessions JOIN and LEAVE the fused decode batch mid-stream (mixed
    prompt lengths, staggered arrivals) and every delivered stream is
    BIT-identical to `model.generate()` with the same sampling config
    and seed — greedy and seeded sampling, run-ahead blocks and
    single-step dispatch alike;
  - admission control IS the KV-slot pool: no free slot ⇒
    `ServeOverloadError` with a positive `retry_after_ms` hint, and
    the session is admitted after a slot frees (mid-stream
    re-admission);
  - a mid-stream deadline expiry frees the slot and the 4th
    reconciliation equation stays exact:
    sessions == completed + failed + expired + shed;
  - chaos soak (injected prefill/decode failures and hangs): zero
    silent token loss — every DELIVERED stream is still bit-exact
    (never torn, never duplicated), every failed session is counted,
    and the reconciliation balances;
  - `warm_decode()` precompiles the dispatch ladder (decode_step,
    every run-ahead rung, every cohort prefill bucket) so mid-stream
    admission never compiles inside a live session's latency budget.
"""
import os
import time

import numpy as np
import pytest

from singa_tpu import device, resilience, serve, stats
from singa_tpu.models.transformer import TransformerLM
from singa_tpu import tensor

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

V, D, H, L = 64, 32, 2, 2
MAXLEN = 16
NEW = 5


@pytest.fixture(autouse=True)
def _clean_decode_config():
    """Decode-serving defaults are process knobs; tracing is a
    process arm — leaving either set would reroute later tests."""
    saved = serve.get_decode_config()
    yield
    device.set_decode_serving(**saved)
    device.set_tracing(False)


@pytest.fixture(scope="module")
def lm():
    """One tiny eval-compiled TransformerLM for the whole module —
    decode executables cache on the model, so sharing it keeps the
    per-test compile cost to the first user of each ladder rung."""
    device.get_default_device().SetRandSeed(0)
    tensor.set_matmul_precision("default")
    return _fresh_lm()


def _fresh_lm():
    """A tiny eval-compiled model of its own: nothing in its program
    cache."""
    m = TransformerLM(V, d_model=D, num_heads=H, num_layers=L,
                      max_len=MAXLEN)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                 device=device.get_default_device())],
              is_train=False, use_graph=False)
    m.eval()
    return m


def _prompts(n, lens=(2, 3, 5)):
    rs = np.random.RandomState(7)
    return [rs.randint(0, V, (1, lens[i % len(lens)])).astype(np.int32)
            for i in range(n)]


def _decode_delta(fn):
    """Run `fn` and return the decode-tier counter deltas."""
    d0 = stats.decode_stats().snapshot()
    out = fn()
    d1 = stats.decode_stats().snapshot()
    return out, {k: d1[k] - d0[k] for k in d1
                 if isinstance(d1.get(k), (int, float))}


def _reconciles(dd):
    return dd["sessions"] == (dd["completed"] + dd["failed"]
                              + dd["expired"] + dd["shed"])


def test_join_leave_bit_identity_greedy(lm):
    """Mixed prompt lengths + staggered arrivals: sessions join the
    fused batch at different steps (forcing cohort prefills and slab
    sequence-rung growth) and leave as they finish — every stream is
    bit-identical to the sequential generate() program."""
    prompts = _prompts(9)
    want = [lm.generate(p, NEW) for p in prompts]
    eng = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4, decode_block=4).start()
    try:
        def run():
            replies = []
            for i, p in enumerate(prompts):
                while True:
                    try:
                        replies.append(eng.submit_decode(p, NEW))
                        break
                    except serve.ServeOverloadError as e:
                        time.sleep(e.retry_after_ms / 1e3)
                if i % 3 == 2:
                    time.sleep(0.01)  # stagger: join mid-stream
            return [r.result(timeout=60) for r in replies]
        got, dd = _decode_delta(run)
    finally:
        eng.stop()
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), w)
    assert dd["completed"] == len(prompts)
    assert _reconciles(dd)
    # zero silent loss: every session streamed exactly NEW tokens
    assert dd["tokens_streamed"] == len(prompts) * NEW


def test_seeded_sampling_bit_identity(lm):
    """Sampled sessions (temperature > 0, per-session seed) reproduce
    generate()'s exact key schedule even when fused with OTHER
    sessions: the per-row logits gather + host-side sampler keep the
    PRNG stream per-session, not per-dispatch."""
    prompts = _prompts(6)
    want = [lm.generate(p, NEW, temperature=0.8, top_k=8, seed=i)
            for i, p in enumerate(prompts)]
    eng = serve.ServingEngine(lm, max_sessions=3, max_new_tokens=NEW,
                              prefill_batch=2, decode_block=4).start()
    try:
        replies = []
        for i, p in enumerate(prompts):
            while True:
                try:
                    replies.append(eng.submit_decode(
                        p, NEW, temperature=0.8, top_k=8, seed=i))
                    break
                except serve.ServeOverloadError as e:
                    time.sleep(e.retry_after_ms / 1e3)
        got = [r.result(timeout=60) for r in replies]
    finally:
        eng.stop()
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), w)


def test_decode_block_one_single_step(lm):
    """decode_block=1 (no run-ahead, one token per dispatch) is the
    same program semantically: identical streams."""
    prompts = _prompts(3)
    want = [lm.generate(p, NEW) for p in prompts]
    eng = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4, decode_block=1).start()
    try:
        replies = [eng.submit_decode(p, NEW) for p in prompts]
        got = [r.result(timeout=60) for r in replies]
    finally:
        eng.stop()
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), w)


def test_streaming_tokens_iterator(lm):
    """`reply.tokens()` streams exactly the generated suffix, in
    order, as the fused steps land — the streaming surface carries
    the same bits as the blocking result()."""
    p = _prompts(1)[0]
    want = lm.generate(p, NEW)[0, p.shape[1]:]
    eng = serve.ServingEngine(lm, max_sessions=2, max_new_tokens=NEW,
                              decode_block=2).start()
    try:
        reply = eng.submit_decode(p, NEW)
        streamed = list(reply.tokens(timeout=60))
    finally:
        eng.stop()
    assert streamed == [int(t) for t in want]


def test_slot_exhaustion_sheds_then_readmits(lm):
    """The KV-slot pool is the admission gate: with every slot
    reserved a submit sheds loudly (ServeOverloadError carrying a
    retry hint and counted `shed`), and the SAME session is admitted
    once a slot frees — mid-stream re-admission."""
    prompts = _prompts(3)
    want2 = lm.generate(prompts[2], NEW)
    eng = serve.ServingEngine(lm, max_sessions=2, max_new_tokens=NEW,
                              decode_block=2).start()
    try:
        def run():
            r0 = eng.submit_decode(prompts[0], NEW)
            r1 = eng.submit_decode(prompts[1], NEW)
            with pytest.raises(serve.ServeOverloadError) as ei:
                eng.submit_decode(prompts[2], NEW)
            assert ei.value.retry_after_ms > 0
            r0.result(timeout=60)
            r1.result(timeout=60)
            # both slots are free again: re-admission succeeds
            deadline = time.time() + 30
            while True:
                try:
                    return eng.submit_decode(
                        prompts[2], NEW).result(timeout=60)
                except serve.ServeOverloadError as e:
                    assert time.time() < deadline
                    time.sleep(e.retry_after_ms / 1e3)
        got, dd = _decode_delta(run)
    finally:
        eng.stop()
    assert np.array_equal(np.asarray(got), want2)
    assert dd["shed"] >= 1
    assert _reconciles(dd)


def test_mid_stream_expiry_frees_slot_and_reconciles(lm):
    """A deadline that lands mid-stream expires the session LOUDLY
    (ServeDeadlineError), frees its slot for queued work, and the
    reconciliation equation stays exact — an expired session is
    counted in exactly one terminal bucket."""
    prompts = _prompts(2)
    want1 = lm.generate(prompts[1], NEW)
    eng = serve.ServingEngine(lm, max_sessions=1, max_new_tokens=NEW,
                              decode_block=1).start()
    try:
        def run():
            doomed = eng.submit_decode(prompts[0], NEW,
                                       deadline_ms=0.01)
            with pytest.raises((serve.ServeDeadlineError,
                                TimeoutError)):
                doomed.result(timeout=60)
            # the slot is back: the next session is admitted and exact
            deadline = time.time() + 30
            while True:
                try:
                    return eng.submit_decode(
                        prompts[1], NEW).result(timeout=60)
                except serve.ServeOverloadError as e:
                    assert time.time() < deadline
                    time.sleep(e.retry_after_ms / 1e3)
        got, dd = _decode_delta(run)
    finally:
        eng.stop()
    assert np.array_equal(np.asarray(got), want1)
    assert dd["expired"] == 1
    assert dd["completed"] == 1
    assert _reconciles(dd)


def test_chaos_soak_zero_silent_token_loss(lm):
    """Injected prefill failures, decode-step failures, and hangs:
    every DELIVERED stream is still bit-exact (a retried block
    recomputes from the unchanged slab — never torn, never
    duplicated), every casualty is a LOUD error in a terminal
    bucket, and the reconciliation balances."""
    prompts = _prompts(12)
    want = [lm.generate(p, NEW) for p in prompts]
    inj = resilience.FaultInjector(seed=3, schedule={
        "prefill_fail": 0.15,
        "decode_fail": 0.15,
        "decode_hang": 0.1,
    }, hang_s=0.001)
    eng = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4, decode_block=2,
                              max_retries=1, backoff_ms=0.1,
                              max_restarts=100,
                              fault_injector=inj).start()
    try:
        def run():
            replies = []
            for p in prompts:
                while True:
                    try:
                        replies.append(eng.submit_decode(p, NEW))
                        break
                    except serve.ServeOverloadError as e:
                        time.sleep(max(e.retry_after_ms, 0.1) / 1e3)
            out = []
            for r in replies:
                try:
                    out.append(r.result(timeout=60))
                except (serve.ServeDispatchError,
                        serve.ServeDeadlineError):
                    out.append(None)
            return out
        got, dd = _decode_delta(run)
    finally:
        eng.stop()
    delivered = sum(1 for g in got if g is not None)
    for g, w in zip(got, want):
        if g is not None:
            assert np.array_equal(np.asarray(g), w)
    assert delivered == dd["completed"]
    assert dd["failed"] == len(prompts) - delivered
    assert _reconciles(dd)
    # accounting, not just identity: completed sessions streamed all
    # their tokens; failed ones never smuggled a partial stream into
    # a delivered result
    assert delivered >= 1  # the soak must actually deliver something
    assert dd["failed"] >= 1  # ... and actually injure something


def test_warm_decode_precompiles_ladder(lm):
    """warm_decode() builds the slab and compiles the dispatch ladder
    up front (> 0 executables touched) and the engine serves
    bit-exactly afterwards — admission never compiles mid-stream."""
    p = _prompts(1)[0]
    want = lm.generate(p, NEW)
    eng = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4, decode_block=4).start()
    try:
        warmed = eng.warm_decode(prompt_lens=(2, 3, 5),
                                 max_new_tokens=NEW)
        got = eng.submit_decode(p, NEW).result(timeout=60)
    finally:
        eng.stop()
    assert warmed > 0
    assert np.array_equal(np.asarray(got), want)


def test_warm_decode_compiles_the_samplers_traffic_will_use(lm):
    """`warm_decode(samplers=[(temperature, top_k)])` compiles that
    pair's sampling program too (greedy has none), so a sampled
    session admitted afterwards traces nothing inside its first
    token's budget: no decode-tier retrace, no new entry in the
    model's program cache, and its stream is `generate()`'s."""
    p = _prompts(1)[0]
    want = lm.generate(p, NEW, temperature=0.7, top_k=8, seed=5)
    eng = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4, decode_block=4).start()
    try:
        plain = eng.warm_decode(prompt_lens=(2, 3, 5), max_new_tokens=NEW,
                                samplers=[(0.0, 0)])
        warmed = eng.warm_decode(prompt_lens=(2, 3, 5),
                                 max_new_tokens=NEW,
                                 samplers=[(0.0, 0), (0.7, 8)])
        held = len(lm._gen_cache)
        traced = stats.cache_stats()["decode"]["retraces"]
        got = eng.submit_decode(p, NEW, temperature=0.7, top_k=8,
                                seed=5).result(timeout=60)
    finally:
        eng.stop()
    assert warmed == plain + 1
    assert len(lm._gen_cache) == held
    assert stats.cache_stats()["decode"]["retraces"] == traced
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("block", [1, 4])
def test_a_greedy_step_after_warm_decode_compiles_nothing(block):
    """`warm_decode` warms the token program of a single greedy step
    (`decode_scan`, k = 1) beside `decode_step`: greedy sessions
    admitted afterwards, whose sessions leave one by one so that single
    steps run, trace no decode program and add none to the model's
    cache, with `decode_block` 1 and with run-ahead blocks."""
    m = _fresh_lm()
    prompts = _prompts(3)
    want = [m.generate(p, n) for p, n in zip(prompts, (3, 4, 5))]
    eng = serve.ServingEngine(m, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4, decode_block=block).start()
    try:
        cold = stats.cache_stats()["decode"]["retraces"]
        warmed = eng.warm_decode(prompt_lens=(2, 3, 5), max_new_tokens=NEW)
        # the step, its token program, the blocks' rungs, 3 cohort
        # sizes x 3 prompt buckets
        assert warmed == 2 + {1: 0, 4: 2}[block] + 9
        held = len(m._gen_cache)
        traced = stats.cache_stats()["decode"]["retraces"]
        assert traced - cold == warmed
        replies = [eng.submit_decode(p, n)
                   for p, n in zip(prompts, (3, 4, 5))]
        got = [r.result(timeout=60) for r in replies]
    finally:
        eng.stop()
    assert len(m._gen_cache) == held
    assert stats.cache_stats()["decode"]["retraces"] == traced
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), w)


def test_decode_steps_tokens_counts_the_steps_that_returned_tokens(lm):
    """`cache_stats()["decode"]["decode_steps_tokens"]`: every step of
    all-greedy traffic (single steps and blocks' steps alike), none of
    the steps a sampled session was live in."""
    prompts = _prompts(4)
    eng = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4, decode_block=4).start()
    try:
        _, greedy = _decode_delta(lambda: [
            r.result(timeout=60)
            for r in [eng.submit_decode(p, NEW) for p in prompts]])
        _, sampled = _decode_delta(lambda: eng.submit_decode(
            prompts[0], NEW, temperature=0.8, top_k=8,
            seed=1).result(timeout=60))
    finally:
        eng.stop()
    assert greedy["decode_steps_tokens"] == greedy["decode_steps"] > 0
    assert sampled["decode_steps"] == NEW - 1
    assert sampled["decode_steps_tokens"] == 0
    assert "decode_steps_tokens" in stats.cache_stats()["decode"]


@pytest.mark.parametrize("k", [1, 2])
def test_ties_and_a_nan_row_pick_what_np_argmax_picks(lm, k, monkeypatch):
    """The token program chooses with `jnp.argmax` what the host chose
    with `np.argmax` from the same logits: the FIRST of equal maxima,
    and the first NaN of a row that holds one (NaN counts as the
    largest to both). Planted logits behind `_slot_step`, through
    `decode_step` (the logits as the host gets them) and through
    `decode_scan` (the tokens), a single step and a block."""
    import jax.numpy as jnp

    rows = np.random.default_rng(3).normal(size=(4, V)).astype(np.float32)
    rows[0, [5, 9, 40]] = rows[0].max() + 1.0       # three equal maxima
    rows[1, :] = 0.25                               # every logit equal
    rows[2, [7, 30]] = np.nan                       # NaN twice
    rows[2, 3] = np.inf
    rows[3, 11] = -np.inf
    planted = jnp.asarray(rows)
    monkeypatch.setattr(lm, "_slot_step",
                        lambda p, c, t, po: (planted, c))
    monkeypatch.setattr(lm, "_program_cache", dict)   # keep none of these
    params = lm._decode_params()
    vec = jnp.zeros(4, jnp.int32)

    def slab():
        return lm.new_slab(params, 4, MAXLEN, planted.devices().pop())

    lg, _ = lm.decode_step(params, slab(), vec, vec)
    assert np.array_equal(np.asarray(lg), rows, equal_nan=True)
    want = np.argmax(np.asarray(lg), -1)
    assert list(want[:3]) == [5, 0, 7]
    toks, _ = lm.decode_scan(params, slab(), vec, vec, k)
    assert toks.shape == (k, 4) and toks.dtype == jnp.int32
    assert np.array_equal(np.asarray(toks), np.tile(want, (k, 1)))


def test_ttft_tpot_spans_under_tracing(lm):
    """The decode tier emits the PR 15 SLO segments: one `ttft` span
    per session (submit → first token) and `tpot` spans for the
    inter-token gaps — the segments `trace.aggregate_fleet` and the SLO
    engine fold into p50/p99."""
    from singa_tpu import trace as trace_mod

    prompts = _prompts(3)
    eng = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4, decode_block=2).start()
    try:
        device.set_tracing(True, ring_capacity=4096)
        trace_mod.clear()
        replies = [eng.submit_decode(p, NEW) for p in prompts]
        for r in replies:
            r.result(timeout=60)
        recs = trace_mod.records()
    finally:
        device.set_tracing(False)
        eng.stop()
    names = [r.get("name") for r in recs]
    assert names.count("ttft") == len(prompts)
    assert names.count("tpot") == len(prompts) * (NEW - 1)
    seg = trace_mod._segment_stats(recs)
    assert seg["ttft"]["count"] == len(prompts)
    assert "p99_ms" in seg["tpot"]


# ---------------------------------------------------------------------------
# The dispatcher's cycle as leaf spans (ISSUE 24)
# ---------------------------------------------------------------------------
_PREFILL = ["decode.prefill." + p
            for p in ("assemble", "dispatch", "readback", "scatter")]
_STEP = ["decode.step." + p
         for p in ("assemble", "dispatch", "readback", "scatter")]


def _cycles(names):
    """Split the dispatcher's leaves, in time order, into cycles: a
    wait for work, or an admit with the phases that followed it."""
    cycles = []
    for n in names:
        if n in ("decode.admit", "decode.wait_work"):
            cycles.append([n])
        else:
            cycles[-1].append(n)
    return cycles


def _assert_cycle_grammar(names):
    """A cycle is a wait, or an admit, a prefill or none, then the
    step's phases or none. A block dispatched behind the one in flight
    comes between that one's dispatch and its readback: its own
    assemble and dispatch, then its predecessor's readback and scatter;
    the last block's readback and scatter close the cycle."""
    assert names[0] in ("decode.admit", "decode.wait_work")
    for cyc in _cycles(names):
        if cyc == ["decode.wait_work"]:
            continue
        assert cyc[0] == "decode.admit", cyc
        rest = cyc[1:]
        if rest[:4] == _PREFILL:
            rest = rest[4:]
        if rest:
            assert rest[:2] == _STEP[:2] and rest[-2:] == _STEP[2:], cyc
            mid = rest[2:-2]
            assert mid == _STEP * (len(mid) // 4), cyc


def _traced_sessions(lm, monkeypatch, n=5, **engine_kw):
    """`n` sessions, each under its own trace id, through a toy
    engine with tracing on: (sessions as the engine made them, the
    ring's records)."""
    from singa_tpu import trace as trace_mod

    made = []

    class Spy(serve._DecodeSession):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(serve, "_DecodeSession", Spy)
    kw = dict(max_sessions=2, max_new_tokens=NEW, prefill_batch=2,
              decode_block=2)
    kw.update(engine_kw)
    eng = serve.ServingEngine(lm, **kw).start()
    try:
        eng.warm_decode(prompt_lens=(2, 3, 5), max_new_tokens=NEW)
        device.set_tracing(True, ring_capacity=8192)
        trace_mod.clear()
        replies = []
        for i, p in enumerate(_prompts(n)):
            with trace_mod.context(f"req-{i}"):
                while True:     # two slots: a shed session comes back
                    try:
                        replies.append(eng.submit_decode(p, NEW))
                        break
                    except serve.ServeOverloadError:
                        time.sleep(0.002)
        for r in replies:
            r.result(timeout=60)
        time.sleep(0.12)        # two empty waits close the timeline
        recs = trace_mod.records()
    finally:
        device.set_tracing(False)
        eng.stop()
    admitted = [s for s in made if s.reply.done()
                and s.reply in replies]
    return admitted, recs


def test_dispatcher_cycle_is_covered_by_ordered_disjoint_leaves(
        lm, monkeypatch):
    """Between its first and its last span the dispatcher thread is
    always inside exactly one leaf, the leaves of a cycle come in the
    loop's order, and the `prefill` / `decode_step` records still lie
    around their dispatch and readback leaves."""
    _, recs = _traced_sessions(lm, monkeypatch)
    leaves = sorted((r for r in recs if r["name"].startswith("decode.")),
                    key=lambda r: r["ts"])
    assert len({r["tid"] for r in leaves}) == 1
    assert all(r["depth"] == 0 for r in leaves)
    _assert_cycle_grammar([r["name"] for r in leaves])
    for a, b in zip(leaves, leaves[1:]):
        assert a["ts"] + a["dur"] <= b["ts"], (a["name"], b["name"])
    covered = sum(r["dur"] for r in leaves)
    spanned = leaves[-1]["ts"] + leaves[-1]["dur"] - leaves[0]["ts"]
    assert covered >= 0.95 * spanned, (covered, spanned)
    names = [r["name"] for r in recs]
    assert names.count("decode.step.readback") == names.count(
        "decode_step") > 0
    assert names.count("decode.prefill.readback") == names.count(
        "prefill") > 0
    # the old records keep their endpoints: from just before the
    # dispatch leaf (or, for a block dispatched behind another, from the
    # end of that one's readback) to just after the readback leaf
    for old, first, last in (("decode_step", "decode.step.dispatch",
                              "decode.step.readback"),
                             ("prefill", "decode.prefill.dispatch",
                              "decode.prefill.readback")):
        olds = [r for r in recs if r["name"] == old]
        firsts = [r for r in leaves if r["name"] == first]
        lasts = [r for r in leaves if r["name"] == last]
        read = None         # the end of the previous readback leaf
        for o, f, la in zip(olds, firsts, lasts):
            if read is not None and f["ts"] < read:
                assert read <= o["ts"] < read + 2e3
                start = read
            else:
                assert o["ts"] <= f["ts"]
                start = f["ts"]
            assert la["ts"] + la["dur"] <= o["ts"] + o["dur"]
            assert (o["dur"] - (la["ts"] + la["dur"] - start)) < 2e3
            read = la["ts"] + la["dur"]
    steps = [r for r in leaves if r["name"] == "decode.step.dispatch"]
    assert {r["args"]["steps"] for r in steps} <= {1, 2}


def test_one_decode_queue_wait_per_admitted_session(lm, monkeypatch):
    """From the session's enqueue to its pop into a cohort, under the
    request's trace id, ending before its first token."""
    admitted, recs = _traced_sessions(lm, monkeypatch)
    assert len(admitted) == 5
    waits = [r for r in recs if r["name"] == "decode_queue_wait"]
    ttft = {r["trace"]: r for r in recs if r["name"] == "ttft"}
    assert sorted(r["trace"] for r in waits) == sorted(
        s.trace[0] for s in admitted)
    by_trace = {s.trace[0]: s for s in admitted}
    for w in waits:
        sess = by_trace[w["trace"]]
        assert w["ts"] == pytest.approx(sess.t_enqueue * 1e6, abs=1e-3)
        first = ttft[w["trace"]]
        assert w["ts"] + w["dur"] <= first["ts"] + first["dur"]
    # five sessions over two slots: some waited for a slot
    assert max(w["dur"] for w in waits) > min(w["dur"] for w in waits)


def test_dispatcher_leaves_reach_the_profilers_host_plane(
        lm, monkeypatch, tmp_path):
    """Live, on the CPU backend: a profiler session around a few toy
    requests holds the dispatcher's leaves under "singa:" in
    /host:CPU, on one line, in the loop's order, and the benchmark's
    reader finds them."""
    import jax
    from jax.profiler import ProfileData

    from perfbench.harness import xplane

    jax.profiler.start_trace(str(tmp_path))
    try:
        _, recs = _traced_sessions(lm, monkeypatch, n=3)
    finally:
        jax.profiler.stop_trace()
    path = xplane.newest_xplane(str(tmp_path))
    tr = xplane.load(path, host_prefix="singa:")
    leaves = [e for e in tr.host if e[0].startswith("singa:decode.")]
    _assert_cycle_grammar([e[0][len("singa:"):] for e in leaves])
    for a, b in zip(leaves, leaves[1:]):
        assert a[2] <= b[1], (a, b)
    in_ring = [r["name"] for r in sorted(recs, key=lambda r: r["ts"])
               if r["name"].startswith("decode.")]
    # the same leaves as the ring holds, but for the one that was open
    # when tracing went off (the ring drops it, its annotation closes)
    in_trace = [e[0][len("singa:"):] for e in leaves]
    assert in_trace[:len(in_ring)] == in_ring
    assert len(in_ring) > 10 and len(in_trace) - len(in_ring) <= 1
    lines = [line for plane in ProfileData.from_file(path).planes
             if plane.name == xplane.HOST_PLANE for line in plane.lines
             if any(e.name.startswith("singa:decode.")
                    for e in line.events)]
    assert len(lines) == 1
