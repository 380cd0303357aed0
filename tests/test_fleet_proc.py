"""Multi-process fleet (ISSUE 13): REAL worker subprocesses behind the
PR 11 `Replica` protocol — `fleet_proc.ProcReplica` over the
`singa_tpu.fleet_worker` entrypoint, framed IPC, heartbeat liveness,
real SIGKILLs, and fleet-wide exact reconciliation across the process
boundary.

Acceptance pins:
  - replies from worker processes are BIT-identical to the unbatched
    forward in the parent (deterministic spec factory, dyadic
    params), across the boundary, a real SIGKILL, failover, and a
    supervisor respawn;
  - a SIGKILLed worker is detected via child exit code (reader EOF),
    its in-flight futures fail with a `ProcTransportError`
    (`ServeDispatchError` subclass) and the router's failover
    re-submits them unchanged; the supervisor respawns the worker
    bounded by max_restarts;
  - respawn is DESERIALIZE-only from the shared prewarmed store:
    worker-reported export hits >= 1, traces == 0 (the heartbeat/
    handshake counters prove it from inside the worker process);
  - missed heartbeats age the health snapshot into the PR 11 stale
    ejection (fail closed) — no special-case code path;
  - per-message IPC deadlines fail the caller with a structured
    transport error instead of hanging on a wedged worker;
  - a torn/corrupt reply frame is REFUSED (CRC), never delivered as
    data — in-flight futures fail loudly and the worker respawns;
  - backpressure: past max_inflight the parent sheds with
    retry_after_ms instead of ballooning the pipe;
  - `fleet.reconcile`'s three equations hold EXACTLY across the
    process boundary (parent-side terminal mirroring), and
    `fleet.reconcile_transport`'s per-generation ledger accounts for
    every request in flight at kill time — killed-in-flight requests
    land in failed/failover, never vanish;
  - the proc chaos soak (tier-1 smoke here; `-m slow` full):
    availability under >= 5% injected faults including real SIGKILLs
    mid-load, zero silent losses;
  - satellite: `tools/serve_health.py --all` over a directory whose
    live snapshots were written by SEPARATE worker processes, mixed
    with stale and garbage files, exits with the worst state;
  - satellite: a SIGKILLed worker's metrics JSONL stays parseable
    via `trace.read_metrics` (crash-flush).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from singa_tpu import device, export_cache, fleet, fleet_proc, \
    resilience, serve, stats, tensor, trace

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

FEATS, HIDDEN, CLASSES, CBATCH = 8, 16, 4, 8


@pytest.fixture(autouse=True)
def _clean_fleet_config():
    saved = fleet.get_config()
    saved_serve = serve.get_config()
    saved_res = serve.get_resilience_config()
    yield
    fleet._CONFIG.update(saved)
    serve.configure(**saved_serve)
    serve._RES_CONFIG.update(saved_res)
    export_cache.configure(directory=None, buckets=None)


def _spec(**over):
    s = {"factory": "benchmarks.fleet_factory:create",
         "factory_kwargs": {"feats": FEATS, "hidden": HIDDEN,
                            "classes": CLASSES,
                            "compile_batch": CBATCH},
         "sys_path": [_ROOT],
         "engine": {"max_batch": CBATCH, "max_wait_ms": 1.0}}
    s.update(over)
    return s


def _proc_replicas(n, spec=None, **proc_kwargs):
    proc_kwargs.setdefault("heartbeat_interval_s", 0.1)
    proc_kwargs.setdefault("spawn_timeout_s", 120.0)
    return fleet.make_replicas(n, spec or _spec(), transport="proc",
                               name_prefix="w", **proc_kwargs)


def _reference(device_index=7):
    from benchmarks import fleet_factory

    return fleet_factory.create(
        feats=FEATS, hidden=HIDDEN, classes=CLASSES,
        compile_batch=CBATCH, device_index=device_index)


def _prewarm_store(store):
    """Populate the shared store from a PRISTINE process — the
    documented populate-once-start-N flow (`tools/prewarm.py` runs in
    its own process too). Prewarming from the test process would key
    artifacts on whatever knob state earlier tests left behind, and
    default-knob workers could never hit them."""
    code = (
        f"import sys; sys.path.insert(0, {_ROOT!r})\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from jax.extend.backend import clear_backends\n"
        "clear_backends()\n"
        "from singa_tpu import device, serve\n"
        "from benchmarks import fleet_factory\n"
        f"device.set_export_cache({store!r})\n"
        f"m = fleet_factory.create(feats={FEATS}, hidden={HIDDEN}, "
        f"classes={CLASSES}, compile_batch={CBATCH}, device_index=7)\n"
        f"rows = serve.prewarm_forward(m, [(({FEATS},), 'float32')], "
        f"max_batch={CBATCH})\n"
        "assert all(r['status'] in ('built', 'present') "
        "for r in rows), rows\n"
        "print('PREWARMED', len(rows))\n")
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert "PREWARMED" in out.stdout, out.stdout + out.stderr


def _refs(model, reqs):
    dev = model.param_tensors()[0].device
    return [np.asarray(model.forward_graph(
        tensor.from_numpy(x, device=dev)).data).copy() for x in reqs]


def _dyadic(rs, n, max_rows=2):
    return [(rs.randint(-16, 16,
                        (int(rs.randint(1, max_rows + 1)), FEATS))
             / 8.0).astype(np.float32) for _ in range(n)]


def _snaps():
    s = stats.cache_stats()
    return s["serve"], s["fleet"]


# ---------------------------------------------------------------------------
# The comprehensive tier-1 integration pass: one fleet, every pin that
# needs a real process boundary (spawns are ~1 s each — consolidated
# so tier-1 pays for them once).
# ---------------------------------------------------------------------------
def test_proc_fleet_sigkill_failover_respawn_and_health(tmp_path):
    store = str(tmp_path / "store")
    hdir = tmp_path / "health"
    hdir.mkdir()
    # populate-once-start-N: ONE prewarm pass (pristine process);
    # every worker boot and respawn below must be deserialize-only
    _prewarm_store(store)
    device.set_export_cache(store)
    ref = _reference()
    rs = np.random.RandomState(3)
    reqs = _dyadic(rs, 24)
    refs = _refs(ref, reqs)
    s0, f0 = _snaps()
    reps = _proc_replicas(2, _spec(health_dir=str(hdir)))
    router = fleet.FleetRouter(
        reps, supervise_interval_s=0.01, health_max_age_s=1.0,
        probe_backoff_ms=20.0, max_restarts=3, seed=3).start()
    try:
        # boot is deserialize-only (worker-side counters over the
        # wire prove it from inside the process); warm every replica
        # so BOTH workers touch the store, not just the one the
        # first request routes to
        warmed = router.warmup(reqs[0])
        assert warmed >= 2
        out = router.submit(reqs[0]).result(60)
        assert out.tobytes() == refs[0].tobytes()
        c = reps[0].counters()
        gen1 = {r.name: r.counters() for r in reps}
        for name, cc in gen1.items():
            assert cc["export"]["hits"] >= 1, (name, cc)
            assert cc["export"]["traces"] == 0, (
                f"{name} traced at boot — cold start must be "
                f"deserialize-only: {cc}")
        # separate worker PROCESSES wrote the health snapshots
        pids = set()
        for i in range(2):
            snap = json.loads(
                (hdir / f"w{i}.health.json").read_text())
            pids.add(snap["pid"])
        assert os.getpid() not in pids
        assert len(pids) == 2, "each replica writes from its own pid"

        # real SIGKILL mid-load: queue work on both, kill one
        futs = [router.submit(x) for x in reqs]
        victim = reps[0]
        victim.sigkill()
        for i, f in enumerate(futs):
            got = f.result(60)
            assert got.tobytes() == refs[i].tobytes(), f"request {i}"
        assert all(f.done() for f in futs)
        # the kill was DETECTED (exit code), not arranged; the reaper
        # records it on its own thread, so wait for the record, not
        # for the replies (failover can deliver them first)
        deadline = time.time() + 60
        while (victim.transport_snapshot()["generations"][1]["exit_code"]
               is None and time.time() < deadline):
            time.sleep(0.005)
        snap = victim.transport_snapshot()
        assert snap["generations"][1]["exit_code"] == -9
        # supervisor notices the death (killed flag via reader EOF),
        # then respawns it, deserialize-only again
        deadline = time.time() + 60
        while (router._slots["w0"].state == "ready"
               and time.time() < deadline):
            time.sleep(0.005)
        assert router._slots["w0"].state != "ready", \
            "router never noticed the SIGKILL"
        while (router._slots["w0"].state != "ready"
               and time.time() < deadline):
            time.sleep(0.02)
        assert router._slots["w0"].state == "ready", \
            router.replica_snapshot()
        # warm the respawned generation directly (warmup dispatches
        # without being a routed submit, so the routing equation
        # stays over router traffic only) and prove it loaded from
        # the store
        assert victim.warmup(reqs[0]) >= 1
        out = router.submit(reqs[0]).result(60)
        assert out.tobytes() == refs[0].tobytes()
        c2 = victim.counters()
        assert c2["export"]["hits"] >= 1
        assert c2["export"]["traces"] == 0, (
            f"respawn traced — must be deserialize-only: {c2}")
        assert c2["pid"] != c["pid"], "respawn is a NEW process"

        # the satellite: --all over live snapshots from separate
        # processes + a stale one + garbage, worst state wins
        import importlib.util

        spec_ = importlib.util.spec_from_file_location(
            "serve_health_for_proc_test",
            os.path.join(_ROOT, "tools", "serve_health.py"))
        sh = importlib.util.module_from_spec(spec_)
        spec_.loader.exec_module(sh)
        code, lines = sh.probe_all(str(hdir), max_age_s=30.0)
        assert code == 0, lines
        assert any("pid=" in ln for ln in lines), lines
        stale = {"state": "ready", "reasons": [],
                 "time": time.time() - 3600, "pid": 4242}
        (hdir / "wstale.health.json").write_text(json.dumps(stale))
        code, lines = sh.probe_all(str(hdir), max_age_s=30.0)
        assert code == 2, lines  # a stale READY must not pass
        (hdir / "wstale.health.json").unlink()
        (hdir / "wbad.health.json").write_text("torn{json")
        code, lines = sh.probe_all(str(hdir), max_age_s=30.0)
        assert code == 2, lines
        (hdir / "wbad.health.json").unlink()
    finally:
        router.stop()
    s1, f1 = _snaps()
    rec = fleet.reconcile(s0, s1, f0, f1, replicas=reps)
    assert rec["ok"], rec
    assert rec["transport"], rec["transport_detail"]
    assert rec["fleet_delta"]["failovers"] > 0
    assert rec["fleet_delta"]["failed"] == 0
    # the clean generations shipped their final counters (handshake)
    snaps = [r.transport_snapshot() for r in reps]
    assert any(g["handshake"] is not None
               for s in snaps for g in s["generations"].values())


def test_proc_replica_refuses_a_tpu_its_parent_holds(monkeypatch):
    """One process per chip: a parent that has initialised jax on the
    TPU holds it, so a worker could never get the device. The spawn
    must fail AT ONCE with the rule and the supported shape — not run
    into spawn_timeout_s — and start no process."""
    import jax

    assert device.backend_initialized()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for mode in ("spawn", "listen"):
        rep = fleet_proc.ProcReplica("w0", _spec(), mode=mode,
                                     spawn_timeout_s=120.0)
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="one process per chip"
                           ) as ei:
            rep.start()
        assert time.perf_counter() - t0 < 5.0
        assert "transport='engine'" in str(ei.value)
        assert "EngineReplica" in str(ei.value)
        assert rep._proc is None
        rep.kill()


def test_missed_heartbeats_eject_fail_closed():
    """A wedged worker stops heartbeating: the snapshot AGES and the
    router's existing stale ejection fires — missed heartbeat =>
    stale => ejected, exactly the PR 11 path (no new code path to
    trust)."""
    reps = _proc_replicas(1, heartbeat_interval_s=10.0)
    router = fleet.FleetRouter(
        reps, supervise_interval_s=0.02, health_max_age_s=0.4,
        probe_backoff_ms=30.0, max_restarts=0, seed=5).start()
    try:
        # the boot heartbeat makes it READY; with a 10 s interval the
        # next one never lands inside health_max_age_s => ejected
        deadline = time.time() + 20
        while (router._slots["w0"].state != "ejected"
               and time.time() < deadline):
            time.sleep(0.02)
        assert router._slots["w0"].state == "ejected", \
            router.replica_snapshot()
        with pytest.raises(fleet.FleetUnavailableError):
            router.submit(np.ones((1, FEATS), np.float32))
    finally:
        router.stop()


def test_ipc_deadline_and_backpressure_and_torn_frame():
    """Three transport guarantees on one worker: (1) a hung dispatch
    fails the caller within the IPC deadline with a structured
    `ProcTransportError` (failover-compatible), (2) past max_inflight
    the parent sheds with retry_after_ms instead of ballooning the
    pipe, (3) a corrupted reply frame is refused by CRC and the
    worker is killed for respawn — the ledger stays exact through
    all of it."""
    s0, _ = _snaps()
    reps = _proc_replicas(1, ipc_deadline_ms=400.0, max_inflight=2)
    r = reps[0].start()
    try:
        x = np.ones((1, FEATS), np.float32)
        r.submit(x).result(30)  # warm

        # (1) hang the next dispatch well past the IPC deadline
        r.hang_once(1.5)
        t0 = time.perf_counter()
        f = r.submit(x)
        with pytest.raises(fleet_proc.ProcTransportError):
            f.result(10)
        waited = time.perf_counter() - t0
        assert waited < 1.4, f"IPC deadline did not bound the wait "\
                             f"({waited:.2f}s)"
        assert isinstance(f._error, serve.ServeDispatchError)
        # let the hung dispatch finish so its (failed) entry's late
        # frame arrives and the pipe is empty again
        deadline = time.time() + 20
        while r.depth() and time.time() < deadline:
            time.sleep(0.02)
        assert r.depth() == 0

        # (2) with the next dispatch hung, two in-flight requests
        # saturate max_inflight=2 — the third sheds with a
        # structured hint
        r.hang_once(0.8)
        f1 = r.submit(x)
        f2 = r.submit(x)
        with pytest.raises(serve.ServeOverloadError) as ei:
            r.submit(x)
        assert ei.value.retry_after_ms > 0
        for fut in (f1, f2):
            try:
                fut.result(30)
            except serve.ServeDispatchError:
                pass  # swept by the deadline — still a loud terminal

        # let the worker finish its hangs so the ledger quiesces
        deadline = time.time() + 20
        while r.depth() and time.time() < deadline:
            time.sleep(0.02)

        # (3) torn frame: the next reply is corrupted in the worker's
        # framer; the CRC check must refuse it and fail closed
        r.tear_next_frame()
        f3 = r.submit(x)
        with pytest.raises(serve.ServeDispatchError):
            f3.result(30)
        assert r.torn_frames_detected >= 1
        assert r.killed, "corrupt stream must kill the worker " \
                         "(respawn is the only safe resync)"
        r.restart()
        out = r.submit(x).result(30)
        assert out is not None
    finally:
        r.stop()
    s1, _ = _snaps()
    d = {k: s1[k] - s0[k] for k in serve.TERMINAL_KEYS}
    assert d["requests"] == (d["replies"] + d["expired"] + d["shed"]
                             + d["dropped"] + d["overflowed"]
                             + d["failed"]), d
    assert d["shed"] >= 1
    tr = fleet.reconcile_transport([r])
    assert tr["ok"], tr


def test_worker_metrics_jsonl_survives_sigkill(tmp_path):
    """Crash-flush satellite: the worker's serving metrics JSONL is
    flush-per-record, so a REAL SIGKILL leaves a parseable log —
    `trace.read_metrics` reads the completed records and skips at
    most one partial trailing line."""
    mpath = str(tmp_path / "w0.worker.jsonl")
    reps = _proc_replicas(1, _spec(metrics_path=mpath))
    r = reps[0].start()
    x = np.ones((1, FEATS), np.float32)
    for _ in range(3):
        r.submit(x).result(30)
    r.sigkill()
    deadline = time.time() + 20
    while not r.killed and time.time() < deadline:
        time.sleep(0.02)
    assert r.killed
    recs = trace.read_metrics(mpath)
    assert recs, "killed worker left no parseable metrics"
    assert all("step" in rec for rec in recs)
    # pin the skip explicitly: a torn trailing record must not break
    # the reader (a kill mid-write is exactly this artifact)
    with open(mpath, "a", encoding="utf-8") as f:
        f.write('{"schema": 1, "step": 99, "rows":')
    assert len(trace.read_metrics(mpath)) == len(recs)
    r._reap(expected=True)


# ---------------------------------------------------------------------------
# The proc chaos soak: tier-1 smoke + the slow full run
# ---------------------------------------------------------------------------
def _proc_chaos_soak(n_requests, seed, kill_steps, n_replicas=2,
                     rate=120.0, store=None):
    """Poisson load over N worker PROCESSES under injected faults
    including REAL SIGKILLs mid-load. Returns (availability, fleet
    deltas, kills fired); asserts zero silent losses, bit-identical
    replies, and exact reconciliation incl. the transport ledger."""
    if store:
        _prewarm_store(store)
        device.set_export_cache(store)
    ref = _reference()
    rs = np.random.RandomState(seed)
    reqs = _dyadic(rs, n_requests)
    refs = _refs(ref, reqs)
    spec = _spec(engine={"max_batch": CBATCH, "max_wait_ms": 1.0,
                         "max_retries": 1, "backoff_ms": 0.2,
                         "shed_watermark": 256,
                         "max_restarts": 1000},
                 injector={"seed": seed, "schedule": {
                     "dispatch_fail": 0.03,
                     "dispatch_hang": 0.02,
                     # step-SET form must survive the spec's JSON
                     # trip to the worker (one poisoned request)
                     "poison_request": {7},
                 }, "hang_s": 0.004})
    finj = resilience.FaultInjector(seed=seed, schedule={
        "proc_sigkill": set(kill_steps),
        "proc_hang": 0.01,
        "pipe_stall": 0.01,
        "torn_frame": 0.005,
        "stale_health": 0.01,
    }, hang_s=0.02)
    reps = _proc_replicas(n_replicas, spec)
    s0, f0 = _snaps()
    router = fleet.FleetRouter(
        reps, fault_injector=finj, supervise_interval_s=0.01,
        health_max_age_s=1.5, probe_backoff_ms=20.0,
        max_restarts=100, max_failover_hops=3, seed=seed).start()
    gaps = rs.exponential(1.0 / rate, n_requests)
    futures, refused = [], 0
    t0 = time.perf_counter()
    due = 0.0
    for i, x in enumerate(reqs):
        due += gaps[i]
        now = time.perf_counter() - t0
        if now < due:
            time.sleep(due - now)
        try:
            futures.append((i, serve.submit_with_backoff(
                router.submit, x, seed=seed, max_attempts=3,
                max_sleep_s=0.05)))
        except (serve.ServeOverloadError, serve.ServeQueueFullError,
                serve.ServeClosedError, fleet.FleetUnavailableError):
            refused += 1
    delivered = failed = 0
    for i, r in futures:
        try:
            out = r.result(120)
        except (serve.ServeDispatchError, serve.ServeDeadlineError,
                serve.ServeClosedError, serve.ServeOverloadError,
                fleet.FleetUnavailableError):
            failed += 1
            continue
        # bit-identity survives the process boundary, retries,
        # failover hops, REAL SIGKILLs, and supervisor respawns
        assert out.tobytes() == refs[i].tobytes(), f"request {i}"
        delivered += 1
    router.stop()
    # zero silent losses: every submitted future resolved
    assert all(r.done() for _, r in futures)
    assert delivered + failed == len(futures)
    s1, f1 = _snaps()
    rec = fleet.reconcile(s0, s1, f0, f1, replicas=reps)
    assert rec["ok"], rec
    fd = rec["fleet_delta"]
    # submit_with_backoff may re-submit on sheds, so router requests
    # can exceed the client's accepted futures — never undercount
    assert fd["requests"] >= len(futures)
    availability = delivered / max(len(futures), 1)
    kills = (f1["kills_injected"] - f0["kills_injected"])
    return availability, {k: f1[k] - f0[k] for k in f1
                          if k != "per_replica"}, kills, reps


def test_proc_chaos_soak_smoke(tmp_path):
    """Tier-1 smoke: short Poisson run over 2 worker processes with
    ONE real SIGKILL mid-load (the full >= 95% / >= 2-SIGKILL soak is
    the `-m slow` test below). Hermetic: workers inherit the CPU
    platform pin and the tmp-path store."""
    availability, fd, kills, reps = _proc_chaos_soak(
        60, seed=11, kill_steps={20}, rate=100.0,
        store=str(tmp_path / "store"))
    assert kills >= 1, "no real SIGKILL fired"
    assert availability > 0.7, f"availability {availability:.3f}"
    # the killed generation's exit code proves a real SIGKILL
    codes = [g["exit_code"]
             for r in reps for g in
             r.transport_snapshot()["generations"].values()]
    assert -9 in codes, codes


@pytest.mark.slow
def test_proc_chaos_soak_full(tmp_path):
    """The acceptance soak: sustained Poisson load over worker
    processes, >= 5% injected faults with >= 2 REAL SIGKILLs
    mid-load — availability >= 95%, zero silent losses,
    bit-identical replies, exact reconciliation incl. the transport
    ledger, supervisor respawns observed and deserialize-only."""
    availability, fd, kills, reps = _proc_chaos_soak(
        300, seed=13, kill_steps={60, 180}, rate=100.0,
        store=str(tmp_path / "store"))
    assert kills >= 2, "need >= 2 real SIGKILLs"
    assert fd["restarts"] >= 1, "supervisor never respawned a kill"
    assert availability >= 0.95, f"availability {availability:.3f}"
    # respawned workers deserialize-only: the LIVE generation's
    # worker-side export counters (over the wire) show loads, no
    # traces
    for r in reps:
        if r.restarts and r._alive():
            c = r.counters()
            assert c["export"]["traces"] == 0, c
            assert c["export"]["hits"] >= 1, c
    for r in reps:
        r.stop()


# ---------------------------------------------------------------------------
# Distributed tracing across the process boundary (ISSUE 15) — the
# acceptance scenario: a real 2-worker proc fleet produces ONE merged
# Chrome timeline where a single trace_id's spans from >= 2 distinct
# pids nest in causal order under the estimated clock offsets; the
# context survives failover (a real SIGKILL) and a supervisor respawn
# (new generation, same trace propagation); tracing disabled adds
# zero wire bytes and zero spans; tracing enabled keeps the three
# reconciliation equations EXACT.
# ---------------------------------------------------------------------------
def test_proc_fleet_merged_trace_failover_respawn_reconcile(tmp_path):
    device.set_tracing(False)
    trace.clear()  # earlier tests leave spans in the shared ring
    s0, f0 = _snaps()
    reps = _proc_replicas(2)
    router = fleet.FleetRouter(
        reps, supervise_interval_s=0.01, health_max_age_s=1.0,
        probe_backoff_ms=20.0, max_restarts=3, seed=11).start()
    x = np.ones((1, FEATS), np.float32)
    try:
        router.warmup(x)
        # -- disabled first (the workers arm their tracers lazily on
        # the first TRACED request): zero spans anywhere, and no ACK
        # clock stamps ever arrive — the untraced wire is the PR 13
        # wire, byte for byte (payload equality pinned in
        # test_fleet_trace; absence of stamps/spans pins it live)
        for _ in range(3):
            router.submit(x).result(60)
        assert trace.records() == []
        for r in reps:
            t = r.transport_snapshot()
            assert t["spans_received"] == 0
            assert all(g["clock_offset_us"] is None
                       for g in t["generations"].values()), t

        # -- tracing ON: every request births a trace_id
        device.set_tracing(True)
        clean = router.submit(x)
        assert clean.trace is not None
        clean.result(60)
        # hang w0's next dispatch, queue a burst, and SIGKILL it with
        # requests guaranteed in flight: failover keeps their ids
        reps[0].hang_once(1.0)
        futs = [router.submit(np.ones((1, FEATS), np.float32))
                for _ in range(16)]
        tids = [f.trace for f in futs]
        assert all(tids) and len(set(tids)) == 16
        reps[0].sigkill()
        for f in futs:
            f.result(60)
        assert [f.trace for f in futs] == tids, \
            "failover must not re-id a request"
        # supervisor notices the death, then respawns w0 (new
        # generation, new pid) — two-phase wait, the kill detection
        # is asynchronous
        deadline = time.time() + 60
        while (router._slots["w0"].state == "ready"
               and time.time() < deadline):
            time.sleep(0.005)
        assert router._slots["w0"].state != "ready", \
            "router never noticed the SIGKILL"
        while (router._slots["w0"].state != "ready"
               and time.time() < deadline):
            time.sleep(0.02)
        assert router._slots["w0"].state == "ready", \
            router.replica_snapshot()
        # traced requests keep flowing INTO the respawned generation
        # (re-armed at spawn via the spec trace block): drain w1 so
        # routing has exactly one place to go
        router.drain("w1")
        futs2 = [router.submit(np.ones((1, FEATS), np.float32))
                 for _ in range(8)]
        for f in futs2:
            f.result(60)
        assert all(f.replica == "w0" for f in futs2)
        time.sleep(0.5)  # heartbeats ship any still-buffered spans
    finally:
        router.stop()
        device.set_tracing(False)
    # tracing kept the three zero-silent-loss equations EXACT, plus
    # the transport ledger
    s1, f1 = _snaps()
    rec = fleet.reconcile(s0, s1, f0, f1, replicas=reps)
    assert rec["ok"], rec
    assert rec["fleet_delta"]["failovers"] >= 1

    path = str(tmp_path / "merged_trace.json")
    router.export_trace(path)
    evs = json.load(open(path))["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert os.getpid() in pids and len(pids) >= 3, pids

    def tid_of(e):
        return (e.get("args") or {}).get("trace")

    # the acceptance criterion: ONE trace_id whose spans come from
    # >= 2 distinct pids and order causally: submit -> route -> ipc
    # (parent clock, exact) -> worker dispatch -> reply (worker clock
    # under the estimated offset; 5 ms slop absorbs offset error)
    nested = 0
    for t in {tid_of(e) for e in evs if tid_of(e)}:
        spans = {e["name"]: e for e in evs if tid_of(e) == t}
        need = {"submit", "route", "ipc", "dispatch", "reply"}
        if not need <= set(spans):
            continue
        if spans["dispatch"]["pid"] == spans["submit"]["pid"]:
            continue
        assert (spans["submit"]["ts"] <= spans["route"]["ts"]
                <= spans["ipc"]["ts"]), t
        assert spans["dispatch"]["ts"] >= spans["ipc"]["ts"] - 5e3, t
        assert spans["dispatch"]["ts"] <= spans["reply"]["ts"], t
        nested += 1
    assert nested >= 1, "no trace nests across the process boundary"
    # the failover hop rode the SAME trace as its request
    fo = [e for e in evs if e["name"] == "failover"]
    assert fo and all(tid_of(e) in set(tids) for e in fo)
    # the respawned generation (gen 2, a NEW pid) served traced
    # requests — context propagation survived the respawn
    gens = reps[0].transport_snapshot()["generations"]
    assert len(gens) >= 2, gens
    pid2 = gens[max(gens)]["pid"]
    assert any(e["pid"] == pid2 and tid_of(e) for e in evs), \
        "no traced span from the respawned worker generation"
