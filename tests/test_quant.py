"""Int8 quantized inference (ISSUE 19): the byte diet applied to the
forward executable and the KV-cached decode tier.

Acceptance pins:
  - post-training symmetric per-channel weight quantization: bounded
    per-element dequant error, scales shaped per output channel, and
    fp8-ready layout (int8 payload and fp32 scales are SEPARATE
    arrays, never interleaved);
  - the graph forward under `device.set_inference_quant("int8")`
    agrees with fp32 on top-1 and stays inside a bounded max relative
    error on seeded inputs; flipping the knob back restores the fp32
    program bit-exactly;
  - the quantized decode tier is self-consistent: `decode_scan` ==
    k x `decode_step` bitwise, ServingEngine streams reproduce across
    engines, export/`resume_decode` with the packed int8 KV rows
    continues BIT-identically to the unmigrated quantized stream, the
    ledger-replay path (kv=None) reproduces the token stream, and the
    chaos soak delivers only exact streams;
  - `export_slab_rows` ships the PACKED form (int8 payload + fp32
    scale planes — ~4x fewer bytes than fp32 rows) and
    `import_slab_rows` refuses a form mismatch LOUDLY;
  - the quant knob joins `export_cache.knob_fingerprint()` (flip =>
    AOT key miss, never a stale cross-mode load) and `tuning.KNOBS`;
  - `hlo_profile.bytes_accessed` over the OPTIMIZED decode-step HLO
    is STRICTLY lower for int8 at the KV-bound serving geometry
    (long slab, small heads) — the regime the KV byte diet targets.
"""
import os
import time

import numpy as np
import pytest

from singa_tpu import (
    device,
    export_cache,
    hlo_profile,
    quant,
    resilience,
    serve,
    stats,
    tensor,
    tuning,
)
from singa_tpu.models.transformer import TransformerLM

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

V, D, H, L = 64, 32, 2, 2
MAXLEN = 16
NEW = 5


@pytest.fixture(autouse=True)
def _clean_quant_config():
    """The quant mode is a process knob riding stats._CONFIG; decode
    serving defaults and the export store are process arms too —
    leaving any of them set would reroute later tests."""
    saved = serve.get_decode_config()
    yield
    device.set_inference_quant("off")
    device.set_decode_serving(**saved)
    device.set_tracing(False)
    export_cache.configure(directory=None, buckets=None)


@pytest.fixture(scope="module")
def lm():
    """One tiny eval-compiled TransformerLM shared across the module
    (the test_serve_decode fixture idiom: decode executables cache on
    the model, so sharing keeps per-test compile cost down)."""
    dev = device.get_default_device()
    dev.SetRandSeed(0)
    tensor.set_matmul_precision("default")
    m = TransformerLM(V, d_model=D, num_heads=H, num_layers=L,
                      max_len=MAXLEN)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                 device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


def _prompts(n, lens=(2, 3, 5)):
    rs = np.random.RandomState(7)
    return [rs.randint(0, V, (1, lens[i % len(lens)])).astype(np.int32)
            for i in range(n)]


def _wait_streams(replies, min_toks, timeout_s=60.0):
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        if all(len(r._stream) >= min_toks for r in replies):
            return
        time.sleep(0.002)
    raise AssertionError(
        [f"{r.session_id}: {len(r._stream)}" for r in replies])


# -- weight quantization: layout + error bound ------------------------


def test_quantize_weight_symmetric_per_channel_layout():
    """Symmetric per-channel int8: payload strictly in [-127, 127]
    (NO -128 — symmetric grids keep negation exact), scales keepdims
    per output channel, and the fp8-ready layout: payload and scale
    are separate arrays, never an interleaved record."""
    rs = np.random.RandomState(0)
    w = rs.randn(32, 48).astype(np.float32)
    q, s = quant.quantize_weight(w, axis=0)
    assert q.dtype == np.int8 and s.dtype == np.float32
    assert q.shape == w.shape and s.shape == (1, 48)
    assert int(q.min()) >= -127 and int(q.max()) <= 127
    # per-element dequant error is bounded by half a quantization
    # step of that element's channel
    err = np.abs(quant.dequantize_weight(q, s) - w)
    assert np.all(err <= 0.5 * s + 1e-7)
    # zero weights quantize exactly (symmetric grid has a true zero)
    qz, sz = quant.quantize_weight(np.zeros((4, 256), np.float32),
                                   axis=0)
    assert not qz.any()


def _top1_within_margin(ref, got, err, share=0.9):
    """Top-1 of `got` judged against the reference logits `ref`
    [..., V] as PR 21 judges served tokens: a position may pick another
    token than the reference only where the reference itself holds
    that token within `err` of its own choice (a near-tie, which an
    error of that size may decide either way), and at least `share`
    of the positions agree outright."""
    want, pick = ref.argmax(-1), got.argmax(-1)
    margin = (np.take_along_axis(ref, want[..., None], -1)
              - np.take_along_axis(ref, pick[..., None], -1))[..., 0]
    return bool((margin <= err).all()
                and (want == pick).mean() >= share)


def test_forward_top1_parity_bounded_error_and_exact_restore(lm):
    """The graph forward under int8: every logit within the stated
    error (5 % of the largest) of fp32 on seeded inputs, top-1 judged
    by the reference's margin (`_top1_within_margin`: under jax 0.9
    one of the 32 positions is a near-tie and picks the other token),
    eligible weights actually quantized (counter moves), and flipping
    the knob off restores the fp32 program BIT-exactly."""
    dev = device.get_default_device()
    dev.SetRandSeed(0)
    m = TransformerLM(V, d_model=64, num_heads=H, num_layers=L,
                      max_len=MAXLEN)
    x = tensor.from_numpy(np.zeros((4, 8), np.int32), device=dev)
    m.compile([x], is_train=False, use_graph=True)
    m.eval()
    ids = np.random.RandomState(3).randint(0, V, (4, 8)).astype(
        np.int32)
    xt = tensor.from_numpy(ids, device=dev)
    ref = tensor.to_numpy(m(xt))
    c0 = dict(quant.stats_counters())
    device.set_inference_quant("int8")
    got = tensor.to_numpy(m(xt))
    c1 = dict(quant.stats_counters())
    device.set_inference_quant("off")
    back = tensor.to_numpy(m(xt))
    assert c1["weights_quantized"] > c0["weights_quantized"]
    assert not np.array_equal(ref, got)  # int8 actually engaged
    stated = 0.05 * np.max(np.abs(ref))   # the stated error, a logit
    assert np.max(np.abs(ref - got)) < stated
    assert _top1_within_margin(ref, got, stated)
    # what the judgement still refuses: a top-1 the reference holds
    # far below its own (its least likely token, at one position)
    wrong = got.copy()
    wrong[0, 0, ref[0, 0].argmin()] = got[0, 0].max() + 1.0
    assert not _top1_within_margin(ref, wrong, stated)
    np.testing.assert_array_equal(ref, back)


# -- knob plumbing: fingerprint, tuning registry, validation ----------


def test_knob_joins_fingerprint_tuning_and_validates():
    """`inference_quant` is a first-class knob: it keys the AOT store
    via knob_fingerprint (flip => different keys, never a stale
    cross-mode artifact), enumerates in tuning.KNOBS/HLO_KNOBS, and
    rejects unknown modes loudly."""
    base = export_cache.knob_fingerprint()
    assert base["inference_quant"] == "off"
    device.set_inference_quant("int8")
    assert export_cache.knob_fingerprint()["inference_quant"] == "int8"
    assert export_cache.knob_fingerprint() != base
    device.set_inference_quant("off")
    assert export_cache.knob_fingerprint() == base
    assert tuning.KNOBS["inference_quant"] == ("off", "int8")
    assert "inference_quant" in tuning.HLO_KNOBS
    with pytest.raises(ValueError):
        device.set_inference_quant("int4")


def test_quant_flip_orphans_forward_artifact(tmp_path):
    """AOT-store semantics across the mode flip: fp32 and int8
    forward executables live under DIFFERENT keys (flip => miss, not
    a stale load), and flipping back re-hits the fp32 artifact."""
    device.set_export_cache(str(tmp_path))
    dev = device.get_default_device()
    dev.SetRandSeed(0)
    m = TransformerLM(V, d_model=64, num_heads=H, num_layers=L,
                      max_len=MAXLEN)
    x = tensor.from_numpy(np.zeros((4, 8), np.int32), device=dev)
    m.compile([x], is_train=False, use_graph=True)
    m.eval()
    ids = np.random.RandomState(3).randint(0, V, (4, 8)).astype(
        np.int32)
    xt = tensor.from_numpy(ids, device=dev)
    m(xt)
    s1 = stats.cache_stats()["export"]
    device.set_inference_quant("int8")
    m(xt)
    s2 = stats.cache_stats()["export"]
    assert s2["hits"] - s1["hits"] == 0  # never a cross-mode load
    assert s2["misses"] - s1["misses"] >= 1
    device.set_inference_quant("off")
    # a FRESH model under the same knobs re-hits the fp32 artifact
    dev.SetRandSeed(0)
    m2 = TransformerLM(V, d_model=64, num_heads=H, num_layers=L,
                       max_len=MAXLEN)
    m2.compile([x], is_train=False, use_graph=True)
    m2.eval()
    s3 = stats.cache_stats()["export"]
    m2(xt)
    s4 = stats.cache_stats()["export"]
    assert s4["hits"] - s3["hits"] >= 1


# -- decode tier: scan==step, packed export, loud form mismatch -------


def test_import_slab_rows_refuses_form_mismatch(lm):
    """fp32 rows into an int8 slab (or vice versa) is a config error
    across a migration — refused LOUDLY, never coerced."""
    B, T, Dh = 2, 16, D // H
    qslab = lm.new_slab(lm._decode_params_quant(), B, T, None)
    fp_rows = np.zeros((L, 2, H, 4, Dh), np.float32)
    with pytest.raises(ValueError, match="form mismatch"):
        lm.import_slab_rows(qslab, 0, fp_rows)
    fslab = lm.new_slab(lm._decode_params(), B, T, None)
    q_rows = (np.zeros((L, 2, H, 4, Dh), np.int8),
              np.zeros((L, 2, 4), np.float32))
    with pytest.raises(ValueError, match="form mismatch"):
        lm.import_slab_rows(fslab, 0, q_rows)
    # refused before anything was dispatched: the donated slabs live
    assert not qslab[0][0].is_deleted() and not fslab[0].is_deleted()


@pytest.mark.parametrize("quant", ["off", "int8"])
def test_a_frame_in_the_wire_form_written_by_hand_imports_bit_exactly(
        lm, quant):
    """A MIGRATE frame carries [L, 2, H, pos, D] (and, packed, the
    [L, 2, pos] scales), as replicas older than the positions-last
    slab wrote it: element (l, kv, h, t, d) of the frame lands at
    [kv, slot, h, d, t] of layer l and nowhere else, the other rows
    and the tail past `pos` stay as they were, and the export of that
    row is the frame again."""
    B, T, Dh, pos, slot = 3, 16, D // H, 5, 2
    rng = np.random.RandomState(11)
    if quant == "int8":
        params = lm._decode_params_quant()
        frame = (rng.randint(-127, 128, (L, 2, H, pos, Dh)).astype(np.int8),
                 rng.rand(L, 2, pos).astype(np.float32))
    else:
        params = lm._decode_params()
        frame = rng.randn(L, 2, H, pos, Dh).astype(np.float32)
    slab = lm.import_slab_rows(lm.new_slab(params, B, T, None), slot, frame)
    pay = frame[0] if quant == "int8" else frame
    for li in range(L):
        layer = np.asarray(slab[li][0] if quant == "int8" else slab[li])
        assert layer.shape == (2, B, H, Dh, T)
        want = np.zeros_like(layer)
        want[:, slot, :, :, :pos] = pay[li].transpose(0, 1, 3, 2)
        np.testing.assert_array_equal(layer, want)
        if quant == "int8":
            scales = np.zeros((2, B, T), np.float32)
            scales[:, slot, :pos] = frame[1][li]
            np.testing.assert_array_equal(np.asarray(slab[li][1]), scales)
    back = lm.export_slab_rows(slab, slot, pos)
    if quant == "int8":
        np.testing.assert_array_equal(back[0], frame[0])
        np.testing.assert_array_equal(back[1], frame[1])
    else:
        assert back.shape == (L, 2, H, pos, Dh)
        np.testing.assert_array_equal(back, frame)


# -- serving: self-consistency, migration bit-identity, chaos ---------


def test_serve_quant_streams_self_consistent_and_warm(lm):
    """The quantized engine's greedy streams reproduce across two
    independently built engines (slab ladder self-consistency — the
    quant analogue of the fp32 tier's generate() bit-identity), with
    warm_decode precompiling the quantized ladder and health/metrics
    carrying the armed mode."""
    device.set_inference_quant("int8")
    prompts = _prompts(6)
    eng = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4, decode_block=4)
    warmed = eng.warm_decode(prompt_lens=(2, 3, 5),
                             max_new_tokens=NEW)
    eng.start()
    try:
        assert warmed > 0
        assert eng.health()["decode"]["quant"] == "int8"
        got1 = [np.asarray(eng.submit_decode(p, NEW).result(timeout=60))
                for p in prompts]
    finally:
        eng.stop()
    eng2 = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                               prefill_batch=4, decode_block=4).start()
    try:
        got2 = [np.asarray(
            eng2.submit_decode(p, NEW).result(timeout=60))
            for p in prompts]
    finally:
        eng2.stop()
    for a, b in zip(got1, got2):
        np.testing.assert_array_equal(a, b)


def test_serve_quant_migrate_transplant_and_replay():
    """The PR 17 migration contract holds verbatim under int8:
    export mid-stream off engine A, resume on engine B with the
    packed int8 KV transplanted — the continued stream is
    BIT-identical to the unmigrated quantized stream; stripping the
    KV (kv=None, the SIGKILL shape) still reproduces the token
    stream via ledger replay; the checkpoint's kv keeps the
    shape[3]==pos accessor and ships int8."""
    device.set_inference_quant("int8")
    # NEW2 long enough that sessions are still in flight at export —
    # a short session can finish inside the first pow2 run-ahead
    # block before export() runs (the test_fleet_decode idiom), and
    # the module lm's max_len=16 can't hold it: dedicated model.
    NEW2 = 48
    dev = device.get_default_device()
    dev.SetRandSeed(0)
    lm = TransformerLM(V, d_model=D, num_heads=H, num_layers=L,
                       max_len=64)
    lm.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                  device=dev)],
               is_train=False, use_graph=False)
    lm.eval()
    prompts = _prompts(2)
    ref = serve.ServingEngine(lm, max_sessions=2,
                              max_new_tokens=NEW2).start()
    try:
        want = [np.asarray(
            ref.submit_decode(p, NEW2).result(timeout=60))
            for p in prompts]
    finally:
        ref.stop()
    a = serve.ServingEngine(lm, max_sessions=2,
                            max_new_tokens=NEW2).start()
    replies = [a.submit_decode(p, NEW2) for p in prompts]
    _wait_streams(replies, 3)
    ckpts = a.export_decode_sessions()
    a.stop()
    assert len(ckpts) == 2, "sessions completed before export"
    for c in ckpts:
        kv = np.asarray(c["kv"])
        assert kv.dtype == np.int8
        sc = np.asarray(c["kv_scale"])
        assert sc.dtype == np.float32
        # shape[3] == pos accessor (the PR 17 wire contract) holds
        # on the packed payload; the scale plane shares the pos axis
        assert kv.shape[3] == sc.shape[2] >= 3
    b = serve.ServingEngine(lm, max_sessions=2,
                            max_new_tokens=NEW2).start()
    try:
        for c in ckpts:
            got = np.asarray(b.resume_decode(c).result(timeout=60))
            i = next(j for j in range(2)
                     if np.array_equal(prompts[j],
                                       np.asarray(c["prompt"])))
            np.testing.assert_array_equal(got, want[i])
    finally:
        b.stop()
    # ledger replay (kv=None): correctness never rides the KV
    d = serve.ServingEngine(lm, max_sessions=2,
                            max_new_tokens=NEW2).start()
    try:
        for c in ckpts:
            c = dict(c, kv=None, kv_scale=None)
            got = np.asarray(d.resume_decode(c).result(timeout=60))
            i = next(j for j in range(2)
                     if np.array_equal(prompts[j],
                                       np.asarray(c["prompt"])))
            np.testing.assert_array_equal(got, want[i])
    finally:
        d.stop()


def test_serve_quant_chaos_soak_prefix_guard(lm):
    """Chaos soak under int8: injected prefill/decode failures and
    hangs — every DELIVERED stream is bit-exact against the clean
    quantized reference (the prefix guard holds: never torn, never
    duplicated), every casualty is loud, and the 4-equation
    reconciliation balances."""
    device.set_inference_quant("int8")
    prompts = _prompts(8)
    ref = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4,
                              decode_block=2).start()
    try:
        want = [np.asarray(ref.submit_decode(p, NEW).result(timeout=60))
                for p in prompts]
    finally:
        ref.stop()
    inj = resilience.FaultInjector(seed=3, schedule={
        "prefill_fail": 0.15,
        "decode_fail": 0.15,
        "decode_hang": 0.1,
    }, hang_s=0.001)
    d0 = stats.decode_stats().snapshot()
    eng = serve.ServingEngine(lm, max_sessions=4, max_new_tokens=NEW,
                              prefill_batch=4, decode_block=2,
                              max_retries=1, backoff_ms=0.1,
                              max_restarts=100,
                              fault_injector=inj).start()
    try:
        replies = []
        for p in prompts:
            while True:
                try:
                    replies.append(eng.submit_decode(p, NEW))
                    break
                except serve.ServeOverloadError as e:
                    time.sleep(max(e.retry_after_ms, 0.1) / 1e3)
        got = []
        for r in replies:
            try:
                got.append(np.asarray(r.result(timeout=60)))
            except (serve.ServeDispatchError, serve.ServeDeadlineError):
                got.append(None)
    finally:
        eng.stop()
    d1 = stats.decode_stats().snapshot()
    dd = {k: d1[k] - d0[k] for k in d1
          if isinstance(d1.get(k), (int, float))}
    delivered = sum(1 for g in got if g is not None)
    for g, w in zip(got, want):
        if g is not None:
            np.testing.assert_array_equal(g, w)
    assert delivered >= 1
    assert dd["sessions"] == (dd["completed"] + dd["failed"]
                              + dd["expired"] + dd["shed"])


# -- the byte meter: strictly lower at the serving geometry -----------


def test_decode_step_bytes_strictly_lower_at_kv_bound_geometry():
    """`hlo_profile.bytes_accessed` over the OPTIMIZED decode-step
    program: at the KV-bound serving geometry (long slab, small
    heads — the regime the KV byte diet targets) the int8 step
    accesses STRICTLY fewer bytes than fp32 at the same geometry.
    Post-optimization HLO, so a convert that materialized the whole
    fp32 slab would fail here, not hide inside the meter."""
    import jax.numpy as jnp

    dev = device.get_default_device()
    dev.SetRandSeed(0)
    m = TransformerLM(V, d_model=64, num_heads=4, num_layers=2,
                      max_len=128)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                 device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    B, T, Dh = 8, 128, 16
    tok = jnp.zeros((B,), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    cache_fp = m.new_slab(m._decode_params(), B, T, None)
    cache_q = m.new_slab(m._decode_params_quant(), B, T, None)
    assert cache_q[0][0].shape == (2, B, 4, Dh, T)
    b_fp = hlo_profile.bytes_accessed(m.decode_step_hlo(
        m._decode_params(), cache_fp, tok, pos))["total"]
    b_q = hlo_profile.bytes_accessed(m.decode_step_hlo(
        m._decode_params_quant(), cache_q, tok, pos))["total"]
    assert b_fp > 0 and b_q > 0
    assert b_q < b_fp, (b_q, b_fp)
    # and not marginally: the slab carry alone is 4x narrower
    assert b_q < 0.85 * b_fp, (b_q, b_fp)
