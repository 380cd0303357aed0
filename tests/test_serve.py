"""Continuous-batching serving tier (ISSUE 7).

Acceptance pins:
  - concurrent small requests coalesce into ONE fused dispatch, and
    every per-request reply is BIT-identical to the unbatched forward
    on exact (dyadic) arithmetic — pad rows provably inert;
  - `BucketPolicy` under serving traffic: a batch landing exactly on
    a bucket boundary pads nothing, a lone request dispatches alone
    after `max_wait_ms`, a request above the top bucket fails ITS
    future loudly (`BucketOverflowError`) without stopping the
    engine, and 200 random-size requests retrace at most
    `n_buckets()` programs;
  - the admission queue is bounded (full ⇒ loud drop, counted);
  - eval-mode semantics key the export artifact (a train-mode forward
    artifact can never serve inference);
  - prewarm populates every (model, bucket) artifact so a fresh
    worker's serving path is deserialize-only (`--dry-run` lists
    missing);
  - per-request spans thread the tracer, the metrics JSONL carries
    occupancy / pad fraction / rolling percentiles, and
    `cache_stats()["serve"]` exposes the queue/coalesce/bucket
    counters.
"""
import os
import sys
import time

import numpy as np
import pytest

from singa_tpu import device, export_cache, layer, model, serve, \
    stats, tensor, trace

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _clean_serving_config():
    """Serving defaults, the export store, and the bucket policy are
    process knobs — leaving them armed would reroute later tests."""
    saved = serve.get_config()
    yield
    serve.configure(**saved)
    export_cache.configure(directory=None, buckets=None)
    device.set_tracing(False)


class TwoLayer(model.Model):
    def __init__(self):
        super().__init__()
        self.fc1 = layer.Linear(16)
        self.r1 = layer.ReLU()
        self.fc2 = layer.Linear(4)

    def forward(self, x):
        return self.fc2(self.r1(self.fc1(x)))


def _serving_model(feats=8, seed=0, dyadic=True):
    """Eval-compiled TwoLayer; `dyadic=True` quantizes params to
    multiples of 1/16 so batched and unbatched forwards are EXACT in
    fp32 — bit-identity by arithmetic, not by luck."""
    import jax.numpy as jnp

    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    m = TwoLayer()
    m.compile([tensor.from_numpy(np.zeros((8, feats), np.float32),
                                 device=dev)],
              is_train=False, use_graph=True)
    m.eval()
    if dyadic:
        for p in m.param_tensors():
            p.data = jnp.round(p.data * 16.0) / 16.0
    return m


def _dyadic_requests(rs, n, feats=8, max_rows=4):
    return [(rs.randint(-16, 16,
                        (int(rs.randint(1, max_rows + 1)), feats))
             / 8.0).astype(np.float32) for _ in range(n)]


def _serve_snap():
    return stats.cache_stats()["serve"]


# ---------------------------------------------------------------------------
# Coalescing + bit-identity
# ---------------------------------------------------------------------------
def test_coalesces_concurrent_requests_into_one_dispatch():
    m = _serving_model()
    rs = np.random.RandomState(0)
    reqs = [(rs.randint(-16, 16, (1, 8)) / 8.0).astype(np.float32)
            for _ in range(6)]
    s0 = _serve_snap()
    with serve.ServingEngine(m, max_batch=16, max_wait_ms=80.0) as eng:
        replies = [eng.submit(x) for x in reqs]
        outs = [r.result(30) for r in replies]
    s1 = _serve_snap()
    assert s1["dispatches"] - s0["dispatches"] == 1
    assert s1["replies"] - s0["replies"] == 6
    assert s1["max_coalesce"] >= 6
    for o in outs:
        assert o.shape == (1, 4)


def test_replies_bit_identical_to_unbatched_forward():
    """The acceptance gate: every coalesced+padded reply equals the
    request's own unbatched forward BIT-for-bit (dyadic arithmetic:
    exact under any reduction order, so pad rows are provably
    inert)."""
    m = _serving_model()
    rs = np.random.RandomState(1)
    reqs = _dyadic_requests(rs, 25)
    refs = [np.asarray(m.forward_graph(
        tensor.from_numpy(x)).data).copy() for x in reqs]
    s0 = _serve_snap()   # the counters are one set a process
    with serve.ServingEngine(m, max_batch=16, max_wait_ms=5.0) as eng:
        replies = [eng.submit(x) for x in reqs]
        outs = [r.result(30) for r in replies]
    assert (_serve_snap()["dispatches"] - s0["dispatches"]
            < len(reqs))  # actually fused
    for got, ref in zip(outs, refs):
        assert got.shape == ref.shape
        assert got.tobytes() == ref.tobytes()


def test_pad_rows_inert_via_batch_mask():
    """The `batch_mask` idiom over a serving bucket: masked per-row
    outputs of the padded batch reduce bit-identically to the
    unpadded reduction — pad rows contribute exact zeros."""
    import jax.numpy as jnp

    rs = np.random.RandomState(2)
    x = (rs.randint(-16, 16, (5, 8)) / 8.0).astype(np.float32)
    pol = export_cache.BucketPolicy(max_batch=8)
    (xp,), info = export_cache.pad_batch_to_bucket([x], pol)
    assert info["n_bucket"] == 8
    mask = export_cache.batch_mask(5, 8)
    row_sum = jnp.sum(jnp.asarray(xp), axis=1)
    masked = jnp.sum(row_sum * jnp.asarray(mask))
    ref = jnp.sum(jnp.sum(jnp.asarray(x), axis=1))
    assert np.asarray(masked).tobytes() == np.asarray(ref).tobytes()


# ---------------------------------------------------------------------------
# BucketPolicy edge cases under serving traffic (satellite)
# ---------------------------------------------------------------------------
def test_batch_on_bucket_boundary_pads_nothing():
    m = _serving_model()
    rs = np.random.RandomState(3)
    s0 = _serve_snap()
    with serve.ServingEngine(m, max_batch=8, max_wait_ms=60.0) as eng:
        replies = [eng.submit(
            (rs.randint(-16, 16, (2, 8)) / 8.0).astype(np.float32))
            for _ in range(4)]  # 4 x 2 rows == the 8-bucket exactly
        for r in replies:
            r.result(30)
    s1 = _serve_snap()
    assert s1["dispatches"] - s0["dispatches"] == 1
    assert s1["pad_rows"] - s0["pad_rows"] == 0
    assert s1["buckets"].get("8", 0) > s0["buckets"].get("8", 0)


def test_single_request_dispatches_alone_after_wait():
    m = _serving_model()
    s0 = _serve_snap()
    with serve.ServingEngine(m, max_batch=32, max_wait_ms=1.0) as eng:
        out = eng.infer(np.ones((3, 8), np.float32), timeout=30)
    s1 = _serve_snap()
    assert out.shape == (3, 4)
    assert s1["dispatches"] - s0["dispatches"] == 1
    # 3 rows pad to the 4-bucket: exactly one pad row
    assert s1["pad_rows"] - s0["pad_rows"] == 1


def test_overflow_above_top_bucket_is_loud_per_request():
    m = _serving_model()
    s0 = _serve_snap()
    with serve.ServingEngine(m, max_batch=8, max_wait_ms=1.0) as eng:
        with pytest.raises(export_cache.BucketOverflowError,
                           match="exceeds the serving ceiling"):
            eng.submit(np.ones((9, 8), np.float32))
        # the engine keeps serving after the refused request
        out = eng.infer(np.ones((2, 8), np.float32), timeout=30)
    assert out.shape == (2, 4)
    s1 = _serve_snap()
    assert s1["overflowed"] - s0["overflowed"] == 1
    assert s1["replies"] - s0["replies"] == 1


def test_retraces_bounded_under_200_random_size_requests():
    """The provisioning bound, serving-side: 200 random-size requests
    through the engine execute at most n_buckets() distinct forward
    programs."""
    m = _serving_model()
    rs = np.random.RandomState(4)
    with serve.ServingEngine(m, max_batch=64, max_wait_ms=0.5) as eng:
        replies = []
        for _ in range(200):
            n = int(rs.randint(1, 17))
            replies.append(eng.submit(
                (rs.randint(-16, 16, (n, 8)) / 8.0)
                .astype(np.float32)))
        for r in replies:
            assert r.result(60).shape[1] == 4
    fwd = m._jit_fwd
    assert len(fwd._compiled) == 1  # one polymorphic jit
    jitted = next(iter(fwd._compiled.values()))
    n_buckets = export_cache.BucketPolicy(max_batch=64).n_buckets()
    assert jitted._cache_size() <= n_buckets
    snap = _serve_snap()
    assert snap["dispatches"] < 200  # traffic actually coalesced


def test_queue_full_drops_loudly():
    m = _serving_model()
    eng = serve.ServingEngine(m, max_batch=4, max_wait_ms=1.0,
                              max_queue=2)
    # admission-only: exercise the bound without racing the dispatcher
    eng._running = True
    s0 = _serve_snap()
    x = np.ones((1, 8), np.float32)
    eng.submit(x)
    eng.submit(x)
    with pytest.raises(serve.ServeQueueFullError, match="queue full"):
        eng.submit(x)
    assert _serve_snap()["dropped"] - s0["dropped"] == 1
    assert _serve_snap()["queue_depth"] == 2
    eng._running = False
    with pytest.raises(serve.ServeClosedError):
        eng.submit(x)


# ---------------------------------------------------------------------------
# Export-cache integration: eval-mode keying + prewarm (satellites)
# ---------------------------------------------------------------------------
def test_eval_mode_keys_the_knob_fingerprint():
    """A train-mode forward artifact silently reused for inference is
    a correctness bug (BN running-stats vs batch-stats semantics):
    the train/eval mode rides the knob snapshot, so the keys can
    never collide."""
    from singa_tpu import autograd

    saved = autograd.training
    try:
        autograd.training = True
        fp_train = export_cache.knob_fingerprint()
        autograd.training = False
        fp_eval = export_cache.knob_fingerprint()
    finally:
        autograd.training = saved
    assert fp_train["train_mode"] is True
    assert fp_eval["train_mode"] is False
    assert fp_train != fp_eval


def test_train_mode_forward_artifact_never_serves_eval(tmp_path):
    """Same model, same shapes: the training-forward artifact (BN/
    dropout train semantics) and the eval-forward artifact are
    DIFFERENT store entries — switching to eval is a miss, never a
    silent hit on the train-mode program."""
    device.set_export_cache(str(tmp_path))
    m = _serving_model(dyadic=False)
    x = tensor.from_numpy(np.ones((4, 8), np.float32))
    m.train(True)
    m.forward_graph(x)  # train-mode forward: traces + publishes
    s0 = stats.cache_stats()["export"]
    m.eval()
    m.forward_graph(x)  # same shape, eval: MUST miss, not hit
    s1 = stats.cache_stats()["export"]
    assert s1["misses"] - s0["misses"] == 1
    assert s1["hits"] - s0["hits"] == 0


def test_prewarm_populates_store_and_worker_serves_warm(tmp_path):
    """The fleet workflow: prewarm offline, then a FRESH model (same
    topology) serves its first request from the store — deserialize
    only, zero traces."""
    device.set_export_cache(str(tmp_path))
    m = _serving_model()
    rows = serve.prewarm_forward(m, [((8,), "float32")], max_batch=8,
                                 dry_run=True)
    assert [r["status"] for r in rows] == ["missing"] * 4
    rows = serve.prewarm_forward(m, [((8,), "float32")], max_batch=8)
    assert [r["status"] for r in rows] == ["built"] * 4
    assert [r["bucket"] for r in rows] == [1, 2, 4, 8]
    rows = serve.prewarm_forward(m, [((8,), "float32")], max_batch=8,
                                 dry_run=True)
    assert [r["status"] for r in rows] == ["present"] * 4
    # fresh worker, same topology/seed: the request path never traces
    m2 = _serving_model()
    s0 = stats.cache_stats()["export"]
    with serve.ServingEngine(m2, max_batch=8,
                             max_wait_ms=1.0) as eng:
        out = eng.infer(np.ones((3, 8), np.float32), timeout=60)
    s1 = stats.cache_stats()["export"]
    assert out.shape == (3, 4)
    assert s1["hits"] - s0["hits"] == 1
    assert s1["traces"] - s0["traces"] == 0


def test_prewarm_without_store_is_loud():
    m = _serving_model()
    with pytest.raises(RuntimeError, match="armed export cache"):
        serve.prewarm_forward(m, [((8,), "float32")], max_batch=4)


def test_sonnx_model_serves_and_reports_input_specs():
    """ONNX-imported models ride the same serving path (the
    conformance corpus doubles as a serving-compat suite), and
    `input_specs` hands prewarm the per-sample grid for free."""
    sys.path.insert(0, os.path.join(_ROOT, "examples", "onnx"))
    from bert import build_bert_onnx

    from singa_tpu import sonnx

    sm = sonnx.SONNXModel(build_bert_onnx(97, 16, 32, 4, 2, 4, seed=3))
    assert sm.input_specs() == [((16,), "int32")]
    sm.eval()
    ids = np.zeros((2, 16), np.int32)
    ref = np.asarray(sm.forward_graph(
        tensor.from_numpy(ids)).data).copy()
    with serve.ServingEngine(sm, max_batch=4, max_wait_ms=1.0) as eng:
        out = eng.infer(ids, timeout=120)
    assert out.shape == ref.shape
    assert np.array_equal(out, ref)


# ---------------------------------------------------------------------------
# Observability: knobs, spans, metrics JSONL, cache_stats
# ---------------------------------------------------------------------------
def test_set_serving_knob_feeds_engine_defaults():
    saved = serve.get_config()
    try:
        device.set_serving(max_batch=16, max_wait_ms=3.5, max_queue=9)
        cfg = serve.get_config()
        assert (cfg["max_batch"], cfg["max_wait_ms"],
                cfg["max_queue"]) == (16, 3.5, 9)
        m = _serving_model()
        eng = serve.ServingEngine(m)
        assert eng.max_batch == 16
        assert eng.max_wait_s == pytest.approx(0.0035)
        assert eng.max_queue == 9
        # partial update touches only what was passed
        device.set_serving(max_wait_ms=1.0)
        assert serve.get_config()["max_batch"] == 16
        with pytest.raises(ValueError):
            serve.configure(max_batch=0)
        with pytest.raises(KeyError):
            serve.configure(bogus=1)
    finally:
        serve.configure(**saved)


def test_per_request_spans_thread_the_tracer():
    m = _serving_model()
    device.set_tracing(True)
    trace.clear()
    try:
        with serve.ServingEngine(m, max_batch=8,
                                 max_wait_ms=20.0) as eng:
            replies = [eng.submit(np.ones((1, 8), np.float32))
                       for _ in range(3)]
            for r in replies:
                r.result(30)
        names = [r["name"] for r in trace.records()]
        assert names.count("queue_wait") == 3  # one per REQUEST
        for span_name in ("batch_assemble", "dispatch", "reply"):
            assert span_name in names
    finally:
        device.set_tracing(False)


def test_record_span_is_noop_while_disabled():
    assert not trace.enabled()
    s0 = stats.cache_stats()["trace"]["spans"]
    trace.record_span("queue_wait", 0.0, 1.0)
    assert stats.cache_stats()["trace"]["spans"] == s0


def test_metrics_jsonl_carries_serving_slo_fields(tmp_path):
    m = _serving_model()
    mpath = str(tmp_path / "serve.jsonl")
    mlog = trace.MetricsLogger(mpath)
    rs = np.random.RandomState(5)
    with serve.ServingEngine(m, max_batch=8, max_wait_ms=2.0,
                             metrics=mlog) as eng:
        replies = [eng.submit(
            (rs.randint(-16, 16, (1, 8)) / 8.0).astype(np.float32))
            for _ in range(10)]
        for r in replies:
            r.result(30)
    mlog.close()
    recs = trace.read_metrics(mpath)
    assert recs, "no serving metrics records"
    assert sum(r["extra"]["requests"] for r in recs) == 10
    for r in recs:
        x = r["extra"]
        assert 0.0 < x["occupancy"] <= 1.0
        assert 0.0 <= x["pad_fraction"] < 1.0
        assert x["rows"] <= x["bucket"]
        assert x["p50_ms"] is None or x["p50_ms"] >= 0
        assert r["examples_per_sec"] > 0
    assert recs[-1]["extra"]["p99_ms"] >= recs[-1]["extra"]["p50_ms"]


def test_serve_counters_in_cache_stats():
    snap = stats.cache_stats()
    assert "serve" in snap
    for k in ("requests", "replies", "errors", "dropped", "overflowed",
              "dispatches", "coalesce_mean", "max_coalesce",
              "occupancy", "queue_depth", "max_queue_depth",
              "buckets"):
        assert k in snap["serve"], k
    # reset_cache_stats zeroes the serving counters like every cache
    stats.reset_cache_stats()
    s = stats.cache_stats()["serve"]
    assert s["requests"] == 0 and s["dispatches"] == 0
    assert s["buckets"] == {}


def test_stopped_engine_refuses_and_drain_false_fails_queued():
    m = _serving_model()
    eng = serve.ServingEngine(m, max_batch=4, max_wait_ms=1.0)
    with pytest.raises(serve.ServeClosedError, match="not running"):
        eng.submit(np.ones((1, 8), np.float32))
    eng._running = True  # queue without a dispatcher
    r1 = eng.submit(np.ones((1, 8), np.float32))
    s0 = _serve_snap()["errors"]
    eng.stop(drain=False)
    assert r1.done()
    with pytest.raises(serve.ServeClosedError):
        r1.result(0)
    assert _serve_snap()["errors"] - s0 == 1


def test_mixed_signature_requests_dispatch_separately():
    """Two per-sample signatures in one window: each group fuses with
    its own kind; replies keep their shapes."""

    class Pointwise(model.Model):
        def forward(self, x):
            from singa_tpu import autograd

            return autograd.relu(x)

    dev = device.get_default_device()
    m = Pointwise()
    m.compile([tensor.from_numpy(np.zeros((2, 4), np.float32),
                                 device=dev)],
              is_train=False, use_graph=True)
    m.eval()
    with serve.ServingEngine(m, max_batch=8, max_wait_ms=40.0) as eng:
        a = [eng.submit(np.ones((1, 4), np.float32))
             for _ in range(2)]
        b = [eng.submit(np.ones((1, 6), np.float32))
             for _ in range(2)]
        for r in a:
            assert r.result(30).shape == (1, 4)
        for r in b:
            assert r.result(30).shape == (1, 6)
