"""Online SLO engine (ISSUE 20): mergeable streaming quantile
sketches, multi-window burn-rate alerting, per-replica anomaly
detection.

Acceptance pins:
  - `QuantileSketch` holds its documented relative-error bound
    against the exact rank quantile of the raw samples, under
    log-spaced bucketing with a BOUNDED bucket count;
  - merge is EXACT: any partition of a stream, merged in any order,
    is bit-identical (full state: buckets, count, zeros, collapsed,
    max) to one sketch fed every sample — with and without bucket
    collapse in play;
  - collapse is LOUD and exact: `collapsed` equals the ground-truth
    number of samples whose true bucket fell below the kept range;
  - disabled-mode `observe()` is a strict no-op: ZERO allocation
    (tracemalloc pin, PR 5 discipline);
  - worker heartbeats are byte-ABSENT when disabled (PR 15
    discipline): no `slo` key ships, and the router ingests nothing;
  - the Google-SRE multi-window burn-rate alerts walk the exact
    pending -> firing -> resolved lifecycle under a fake clock, and
    a sub-pending blip goes pending -> resolved WITHOUT firing
    (flap suppression);
  - anomaly detectors (heartbeat-gap EWMA, clock offset vs
    uncertainty, counter-rate spikes) fire per-replica alerts that
    NAME the replica;
  - alert records are schema-stable: every record carries the same
    key set;
  - the ServingEngine feeds real segments end to end, and
    `device.set_slo` is the knob.
"""
import json
import math
import os
import random
import tracemalloc

import numpy as np
import pytest

from singa_tpu import device, serve, slo, stats


@pytest.fixture(autouse=True)
def _slo_disarmed():
    """Every test starts and ends with the engine disarmed (module
    state is process-global)."""
    slo.configure(False)
    yield
    slo.configure(False)


def _state(sk):
    """Full observable sketch state, for bit-identity comparison."""
    return (sk.count, sk.zeros, sk.collapsed, sk.max_value,
            tuple(sorted(sk.buckets.items())))


# ---------------------------------------------------------------------------
# sketch: accuracy, merge exactness, collapse
# ---------------------------------------------------------------------------

def test_sketch_holds_relative_error_bound():
    rng = random.Random(0)
    samples = [math.exp(rng.gauss(2.0, 1.5)) for _ in range(5000)]
    sk = slo.QuantileSketch(rel_err=0.02)
    for v in samples:
        sk.add(v)
    samples.sort()
    for q in (0.5, 0.9, 0.99, 0.999):
        exact = slo.rank_quantile(samples, q)
        got = sk.quantile(q)
        assert abs(got - exact) / exact <= 0.02 + 1e-12, (
            f"q={q}: sketch {got} vs exact {exact}")


@pytest.mark.parametrize("max_buckets", [512, 16])
def test_sketch_merge_any_partition_any_order_bit_identical(
        max_buckets):
    """Merge of worker sketches must be bit-identical to one sketch
    fed all samples — including when the bounded bucket budget forces
    collapse (max_buckets=16 over 6 decades of dynamic range)."""
    rng = random.Random(1)
    samples = ([math.exp(rng.gauss(0.0, 3.0)) for _ in range(2000)]
               + [0.0] * 17)  # zeros ride the exact counter
    one = slo.QuantileSketch(0.02, max_buckets)
    for v in samples:
        one.add(v)
    for trial in range(10):
        rng2 = random.Random(100 + trial)
        shuffled = list(samples)
        rng2.shuffle(shuffled)
        nparts = rng2.randint(2, 7)
        parts = [shuffled[i::nparts] for i in range(nparts)]
        sketches = []
        for part in parts:
            sk = slo.QuantileSketch(0.02, max_buckets)
            for v in part:
                sk.add(v)
            sketches.append(sk)
        rng2.shuffle(sketches)
        merged = sketches[0]
        for sk in sketches[1:]:
            merged.merge(sk)
        assert _state(merged) == _state(one), (
            f"trial {trial}: merge order/partition changed the state")


def test_sketch_collapse_is_loud_and_exact():
    """`collapsed` == ground-truth count of samples whose true bucket
    index fell below the kept range, and only the LOW tail is biased:
    high quantiles still hold the bound."""
    B = 16
    rng = random.Random(2)
    samples = [math.exp(rng.uniform(-8.0, 8.0)) for _ in range(3000)]
    sk = slo.QuantileSketch(0.02, B)
    for v in samples:
        sk.add(v)
    assert len(sk.buckets) <= B
    idxs = [int(math.ceil(math.log(v) / math.log(sk.gamma)))
            for v in samples]
    floor = max(idxs) - B + 1
    truth = sum(1 for i in idxs if i < floor)
    assert truth > 0, "test must actually exercise collapse"
    assert sk.collapsed == truth
    samples.sort()
    exact99 = slo.rank_quantile(samples, 0.99)
    assert abs(sk.quantile(0.99) - exact99) / exact99 <= 0.02 + 1e-12


def test_sketch_zeros_and_wire_roundtrip():
    sk = slo.QuantileSketch(0.02, 64)
    for v in (0.0, -1.0, 0.5, 2.0, 2.0, 100.0):
        sk.add(v)
    assert sk.zeros == 2 and sk.count == 6
    w = sk.to_wire()
    json.dumps(w)  # must be JSONL-able as-is
    back = slo.QuantileSketch.from_wire(w)
    assert _state(back) == _state(sk)
    assert back.snapshot() == sk.snapshot()


def test_sketch_shape_mismatch_refuses_merge():
    a = slo.QuantileSketch(0.02, 64)
    b = slo.QuantileSketch(0.05, 64)
    with pytest.raises(ValueError):
        a.merge(b)


# ---------------------------------------------------------------------------
# disabled discipline: zero-allocation no-op, byte-absent payloads
# ---------------------------------------------------------------------------

def test_disabled_observe_allocates_nothing():
    """PR 5 discipline: the disabled hot path is two loads and a
    return.  CPython attributes occasional frame-object/freelist
    churn to the `def` line (a few hundred bytes, NOT proportional to
    call count), so the pin is amortized: the smallest alloc a per-
    call leak could make is a 24-byte float/tuple per call = 48KB
    over 2000 calls; we demand well under 1 byte/call."""
    assert not slo.enabled()
    N = 2000
    only_slo = tracemalloc.Filter(True, "*slo.py")
    rounds = []
    tracemalloc.start()
    try:
        for _ in range(3):
            for _ in range(50):  # warm frames/freelists
                slo.observe("queue_wait", 0.001)
                slo.observe_outcome(True)
            before = tracemalloc.take_snapshot().filter_traces(
                [only_slo])
            for _ in range(N):
                slo.observe("queue_wait", 0.001)
                slo.observe_outcome(True)
            after = tracemalloc.take_snapshot().filter_traces(
                [only_slo])
            rounds.append(sum(
                s.size_diff
                for s in after.compare_to(before, "lineno")
                if s.size_diff > 0))
    finally:
        tracemalloc.stop()
    assert min(rounds) < N // 2, (
        f"disabled observe allocates per call: {rounds} bytes "
        f"per {N}-call round")


def test_disabled_payloads_are_none_or_empty():
    assert slo.wire_payload() is None
    assert slo.alert_counts() is None
    assert slo.report() is None
    assert slo.recent_alerts() == []
    assert slo.config() == {}


# ---------------------------------------------------------------------------
# burn-rate alerting: lifecycle, flap suppression (fake clock)
# ---------------------------------------------------------------------------

def _lifecycle(recs, alert, rule):
    return [r["state"] for r in recs
            if r["alert"] == alert and r["rule"] == rule]


def test_availability_burn_alert_full_lifecycle():
    """Deterministic fake-clock walk: sustained 100% failure breaches
    both windows -> pending; still breaching past the pending hold ->
    firing; traffic recovers and the windows drain -> resolved."""
    slo.configure(True, window_scale=1.0,
                  spec={"availability": 0.999})
    # slow rule scaled windows: long 259200s, short 21600s; fast:
    # 3600/300.  Feed bad events in the fast-rule short window.
    t = 1000.0
    for i in range(100):
        slo.observe_outcome(False, now=t + i * 0.1)
    t += 10.0
    slo.tick(now=t)  # breach seen -> pending
    # pending hold = 0.5 * short_s (fast: 150s): tick past it
    slo.tick(now=t + 200.0)  # still in window -> firing
    # drain: fast short window is 300s — past t+310 the bad events
    # leave the short window, burn drops to 0 (empty window), and the
    # recovery must hold resolve_for (= short_s) before resolving
    slo.tick(now=t + 320.0)
    slo.tick(now=t + 320.0 + 301.0)
    states = _lifecycle(slo.recent_alerts(), "availability", "fast")
    assert states == ["pending", "firing", "resolved"], states


def test_blip_goes_pending_resolved_without_firing():
    """Flap suppression: a breach shorter than the pending hold never
    fires — the record shows pending -> resolved, no page."""
    slo.configure(True, window_scale=1.0,
                  spec={"availability": 0.999})
    t = 1000.0
    for i in range(20):
        slo.observe_outcome(False, now=t + i * 0.1)
    slo.tick(now=t + 5.0)  # pending
    # blip over: good traffic floods the window far past the breach
    for i in range(5000):
        slo.observe_outcome(True, now=t + 10.0 + i * 0.05)
    slo.tick(now=t + 300.0)
    slo.tick(now=t + 700.0)  # recovery held past resolve_for
    states = _lifecycle(slo.recent_alerts(), "availability", "fast")
    assert states == ["pending", "resolved"], states
    assert slo.alert_counts()["firing"] == 0


def test_latency_objective_feeds_burn_rules():
    """A per-segment latency objective reduces to good/bad events the
    same burn rules evaluate: sustained threshold misses page."""
    slo.configure(True, window_scale=1.0, spec={
        "availability": 0.999,
        "latency": {"reply": {"threshold_ms": 10.0,
                              "target": 0.99}}})
    t = 1000.0
    for i in range(100):
        slo.observe("reply", 0.050, now=t + i * 0.1)  # 50ms > 10ms
    slo.tick(now=t + 10.0)
    slo.tick(now=t + 220.0)
    states = _lifecycle(slo.recent_alerts(), "latency:reply", "fast")
    assert states == ["pending", "firing"], states


# ---------------------------------------------------------------------------
# per-replica anomaly detection
# ---------------------------------------------------------------------------

def test_hb_gap_anomaly_names_the_replica():
    slo.configure(True, hb_gap_min_s=0.5, anomaly_pending_s=0.1,
                  anomaly_resolve_s=0.25)
    t = 1000.0
    for i in range(20):  # healthy baseline ~50ms gaps
        slo.note_replica("w1", hb_gap_s=0.05, now=t + i * 0.05)
    slo.note_replica("w1", hb_gap_s=5.0, now=t + 2.0)   # pending
    slo.note_replica("w1", hb_gap_s=6.0, now=t + 2.5)   # firing
    slo.note_replica("w1", hb_gap_s=0.05, now=t + 3.0)
    slo.note_replica("w1", hb_gap_s=0.05, now=t + 4.0)  # resolved
    recs = [r for r in slo.recent_alerts()
            if r["alert"] == "anomaly:hb_gap"]
    assert [r["state"] for r in recs] == ["pending", "firing",
                                          "resolved"]
    assert all(r["replica"] == "w1" for r in recs)
    assert all(r["severity"] == "page" for r in recs)


def test_clock_offset_anomaly_uses_uncertainty():
    slo.configure(True, clock_mult=3.0, clock_slack_us=100.0,
                  anomaly_pending_s=0.1, anomaly_resolve_s=0.25)
    t = 1000.0
    # offset within 3x uncertainty + slack: healthy
    slo.note_replica("w2", clock_offset_us=50.0,
                     clock_uncertainty_us=100.0, now=t)
    assert slo.recent_alerts() == []
    # offset far outside the estimator's own uncertainty: anomaly
    slo.note_replica("w2", clock_offset_us=5000.0,
                     clock_uncertainty_us=100.0, now=t + 1.0)
    slo.note_replica("w2", clock_offset_us=5000.0,
                     clock_uncertainty_us=100.0, now=t + 1.2)
    recs = [r for r in slo.recent_alerts()
            if r["alert"] == "anomaly:clock"]
    assert [r["state"] for r in recs] == ["pending", "firing"]
    assert recs[0]["replica"] == "w2"


def test_counter_spike_anomaly_vs_trailing_baseline():
    """Cumulative-counter deltas over a trailing window: a restart
    burst fires (restarts min_count=1); the steady trickle that built
    the baseline never did."""
    slo.configure(True, spike_window_s=2.0, spike_mult=8.0,
                  anomaly_pending_s=0.1, anomaly_resolve_s=0.25)
    t = 1000.0
    slo.note_replica("w3", counters={"restarts": 0}, now=t)
    for i in range(10):  # quiet: no restarts
        slo.note_replica("w3", counters={"restarts": 0},
                         now=t + 1 + i)
    assert slo.recent_alerts() == []
    slo.note_replica("w3", counters={"restarts": 2}, now=t + 12.0)
    slo.note_replica("w3", counters={"restarts": 2}, now=t + 12.2)
    recs = [r for r in slo.recent_alerts()
            if r["alert"] == "anomaly:rate:restarts"]
    assert [r["state"] for r in recs] == ["pending", "firing"]
    assert recs[0]["replica"] == "w3"


# ---------------------------------------------------------------------------
# alert records: schema stability + JSONL stream
# ---------------------------------------------------------------------------

_ALERT_KEYS = {"schema", "kind", "time", "mono", "alert", "rule",
               "severity", "replica", "state", "episode", "burn_long",
               "burn_short", "value", "threshold"}


def test_alert_records_schema_stable_and_streamed(tmp_path):
    apath = tmp_path / "alerts.jsonl"
    slo.configure(True, window_scale=1.0,
                  spec={"availability": 0.999},
                  alerts_path=str(apath))
    t = 1000.0
    for i in range(100):
        slo.observe_outcome(False, now=t + i * 0.1)
    slo.tick(now=t + 10.0)
    slo.tick(now=t + 220.0)
    recs = [json.loads(ln) for ln in
            apath.read_text().strip().splitlines()]
    assert recs, "alerts JSONL must carry the transitions"
    assert {tuple(sorted(r)) for r in recs} == {
        tuple(sorted(_ALERT_KEYS))}
    assert all(r["schema"] == slo.ALERTS_SCHEMA for r in recs)
    assert all(r["kind"] == "slo_alert" for r in recs)
    # in-memory ring mirrors the stream
    assert [r["state"] for r in recs] == \
        [r["state"] for r in slo.recent_alerts()]


# ---------------------------------------------------------------------------
# wire: cumulative replace, generation fencing
# ---------------------------------------------------------------------------

def test_ingest_is_lww_with_generation_fencing():
    slo.configure(True)
    s0 = stats.cache_stats()["slo"]  # counters are process-global
    sk = slo.QuantileSketch(0.02, 512)
    sk.add(5.0)
    sk.add(7.0)
    payload = {"seg": {"reply": sk.to_wire()}}
    slo.ingest_wire("w0", payload, gen=2)
    # stale generation: refused, loudly counted
    old = slo.QuantileSketch(0.02, 512)
    old.add(1.0)
    slo.ingest_wire("w0", {"seg": {"reply": old.to_wire()}}, gen=1)
    snap = stats.cache_stats()["slo"]
    assert snap["ingests"] - s0["ingests"] == 1
    assert snap["ingests_stale"] - s0["ingests_stale"] == 1
    rep = slo.report()
    assert rep["segments"]["reply"]["count"] == 2
    assert rep["replicas"] == ["w0"]
    # same gen, newer payload: cumulative REPLACE, not accumulate
    sk.add(9.0)
    slo.ingest_wire("w0", {"seg": {"reply": sk.to_wire()}}, gen=2)
    assert slo.report()["segments"]["reply"]["count"] == 3


def test_merged_report_equals_single_stream(tmp_path):
    """Fleet-merged report quantile == one sketch fed all worker
    samples (the tentpole's exactness claim, at the report level)."""
    rng = random.Random(3)
    samples = [math.exp(rng.gauss(1.0, 1.0)) for _ in range(900)]
    one = slo.QuantileSketch(0.02, 512)
    for v in samples:
        one.add(v * 1e3)  # observe() feeds seconds; sketch holds ms
    slo.configure(True)
    for w in range(3):
        sk = slo.QuantileSketch(0.02, 512)
        for v in samples[w::3]:
            sk.add(v * 1e3)
        slo.ingest_wire(f"w{w}", {"seg": {"ipc": sk.to_wire()}},
                        gen=1)
    rep = slo.report()
    assert rep["segments"]["ipc"] == one.snapshot()


# ---------------------------------------------------------------------------
# engine + device knob wiring
# ---------------------------------------------------------------------------

def test_serving_engine_feeds_segments_end_to_end():
    """A real ServingEngine run populates queue_wait/dispatch/reply
    sketches and good outcomes — no bench machinery involved."""
    from benchmarks import fleet_factory

    device.set_slo(True, spec={"availability": 0.999})
    try:
        eng = serve.ServingEngine(
            fleet_factory.create(feats=8, hidden=8, classes=4,
                                 compile_batch=4),
            max_batch=4, max_wait_ms=1.0).start()
        x = np.arange(8, dtype=np.float32).reshape(1, 8) / 8.0
        for _ in range(6):
            eng.submit(x).result(timeout=30.0)
        counts = slo.alert_counts()
        health = eng.health()
        eng.stop()
        r = slo.report()
        for segname in ("queue_wait", "dispatch", "reply"):
            assert r["segments"][segname]["count"] >= 6, segname
        # outcomes are a FLEET-path feed (router _finish), not an
        # engine feed — engine-only traffic leaves them untouched
        assert r["availability"]["good"] == 0
        assert health["alerts"] == counts  # engine surfaces counts
    finally:
        device.set_slo(False)


def test_online_sketches_agree_with_the_traces_own_samples():
    """The cross-validation of the two telemetry paths, on a real
    engine: with tracing and the SLO engine both armed, each request
    segment's online sketch has counted exactly the spans the trace
    holds (`trace.fleet_segment_samples_ms`), and its p99 sits within
    twice the sketch's relative-error bound of the p99 of those raw
    samples under the sketch's own rank convention
    (`slo.rank_quantile`)."""
    from benchmarks import fleet_factory
    from singa_tpu import trace

    rel_err = 0.02
    device.set_tracing(True)
    trace.clear()
    device.set_slo(True, rel_err=rel_err, spec={"availability": 0.999})
    try:
        eng = serve.ServingEngine(
            fleet_factory.create(feats=8, hidden=8, classes=4,
                                 compile_batch=4),
            max_batch=4, max_wait_ms=1.0).start()
        x = np.arange(8, dtype=np.float32).reshape(1, 8) / 8.0
        for _ in range(40):
            eng.submit(x).result(timeout=30.0)
        eng.stop()
        online = slo.report()["segments"]
        samples = trace.fleet_segment_samples_ms(spans=trace.records())
    finally:
        device.set_slo(False)
        device.set_tracing(False)
        trace.clear()
    for seg in ("queue_wait", "dispatch", "reply"):
        assert online[seg]["count"] == len(samples[seg]) >= 40, seg
        assert samples[seg] == sorted(samples[seg])
        exact = slo.rank_quantile(samples[seg], 0.99)
        # the snapshot rounds to a microsecond
        assert abs(online[seg]["p99_ms"] - exact) <= (
            2 * rel_err * exact + 1e-3), (seg, online[seg], exact)


def test_disabled_engine_health_has_no_alerts_key():
    """Byte-identity: with the SLO engine off, health snapshots carry
    no `alerts` key at all (old monitors parse unchanged)."""
    from benchmarks import fleet_factory

    eng = serve.ServingEngine(
        fleet_factory.create(feats=8, hidden=8, classes=4,
                             compile_batch=4),
        max_batch=4, max_wait_ms=1.0).start()
    try:
        assert "alerts" not in eng.health()
    finally:
        eng.stop()


def _proc_spec(with_slo):
    _root = os.path.abspath(
        os.path.join(os.path.dirname(__file__), ".."))
    s = {"factory": "benchmarks.fleet_factory:create",
         "factory_kwargs": {"feats": 8, "hidden": 16, "classes": 4,
                            "compile_batch": 8},
         "sys_path": [_root],
         "engine": {"max_batch": 8, "max_wait_ms": 1.0}}
    if with_slo:
        s["slo"] = slo.config()
    return s


def test_heartbeat_slo_payload_byte_absence_over_proc():
    """PR 15 discipline across the process boundary: a worker armed
    via its spec piggybacks cumulative sketch payloads on heartbeats
    and the parent ingests them; a worker with NO `slo` spec key
    ships no `slo` key at all — the armed parent ingests nothing."""
    import time as _time

    from singa_tpu import fleet

    device.set_slo(True, spec={"availability": 0.999})
    try:
        x = np.arange(8, dtype=np.float32).reshape(1, 8) / 8.0

        # armed worker: spec carries the router's config verbatim
        base = stats.cache_stats()["slo"]["ingests"]
        reps = fleet.make_replicas(
            1, _proc_spec(with_slo=True), transport="proc",
            name_prefix="aw", heartbeat_interval_s=0.1,
            spawn_timeout_s=120.0)
        try:
            reps[0].start()
            reps[0].submit(x).result(30)  # give the worker samples
            deadline = _time.time() + 10.0
            while _time.time() < deadline:
                if stats.cache_stats()["slo"]["ingests"] > base:
                    break
                _time.sleep(0.05)
            assert stats.cache_stats()["slo"]["ingests"] > base
            assert "aw0" in slo.report()["replicas"]
        finally:
            reps[0].stop()

        # unarmed worker: heartbeats are byte-absent of `slo` — the
        # parent engine (still armed) has nothing to ingest
        base = stats.cache_stats()["slo"]["ingests"]
        reps = fleet.make_replicas(
            1, _proc_spec(with_slo=False), transport="proc",
            name_prefix="uw", heartbeat_interval_s=0.1,
            spawn_timeout_s=120.0)
        try:
            reps[0].start()
            reps[0].submit(x).result(30)
            _time.sleep(0.6)  # several heartbeat intervals
            assert stats.cache_stats()["slo"]["ingests"] == base
            assert "uw0" not in slo.report()["replicas"]
        finally:
            reps[0].stop()
    finally:
        device.set_slo(False)


def test_set_slo_knob_arms_and_resets():
    device.set_slo(True, rel_err=0.01, window_scale=0.5)
    assert slo.enabled()
    cfg = slo.config()
    assert cfg["rel_err"] == 0.01 and cfg["window_scale"] == 0.5
    slo.observe("ipc", 0.002)
    assert slo.report()["segments"]["ipc"]["count"] == 1
    # re-arming builds a FRESH engine (documented reset semantics)
    device.set_slo(True)
    assert slo.report()["segments"] == {}
    device.set_slo(False)
    assert not slo.enabled()
