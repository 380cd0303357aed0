"""Fleet-wide KV-cached decode (ISSUE 17): session-affine routing,
live KV-slab migration, and SIGKILL-proof streaming generation.

Acceptance pins:
  - `FleetRouter.submit_decode` places sessions by per-replica
    KV-slot occupancy (most free slots first) with session-id
    stickiness layered on top; a full fleet sheds LOUDLY with
    `ServeOverloadError.retry_after_ms` as the backpressure currency;
  - `drain(name)` with LIVE decode sessions checkpoints each one
    (KV slab + generated-token ledger + PRNG key schedule + deadline
    remainder) and the SAME `FleetDecodeReply` proxy keeps yielding
    from the target replica — zero token loss, zero duplicates,
    stream bit-identical to single-engine `generate()`;
  - engine-level `export_decode_sessions`/`resume_decode` round-trip
    bit-identically on BOTH paths: KV transplant (fast) and ledger
    re-prefill replay (`kv=None` — correctness never depends on the
    checkpoint's KV);
  - a replica killed mid-generation (in-process kill or REAL
    SIGKILL over the proc transport) triggers ledger REPLAY on
    another replica from the proxy's delivered stream — resumed
    sessions still bit-identical, failures loud, never torn;
  - the PR 16 session equation joins `fleet.reconcile` fleet-wide:
    sessions == completed + failed + expired + shed, with
    migrated/resumed netting to zero once every hand-off lands, plus
    the router-level decode terminal equation
    (decode_requests == decode_replies + decode_failed +
    decode_rejected) — both EXACT at quiescence;
  - a SIGKILLed worker's respawn re-runs `warm_decode()` from the
    spec and, with the shared export-cache store populated by the
    first generation, is DESERIALIZE-only: worker-side counters over
    the wire pin export hits >= 1 and traces == 0.
"""
import os
import time

import numpy as np
import pytest

from singa_tpu import device, export_cache, fleet, serve, stats

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

V, MAXLEN = 64, 64


@pytest.fixture(autouse=True)
def _clean_config():
    saved = fleet.get_config()
    saved_serve = serve.get_config()
    saved_decode = serve.get_decode_config()
    yield
    fleet._CONFIG.update(saved)
    serve.configure(**saved_serve)
    device.set_decode_serving(**saved_decode)
    device.set_tracing(False)
    export_cache.configure(directory=None, buckets=None)


@pytest.fixture(scope="module")
def lm():
    """One shared eval-compiled LM: the bit-identity oracle and the
    engine under test for the engine-level migration pins."""
    from benchmarks import fleet_factory

    return fleet_factory.create_lm(vocab=V, max_len=MAXLEN,
                                   device_index=7)


def _prompts(n, lens=(2, 3, 5, 4)):
    rs = np.random.RandomState(7)
    return [rs.randint(0, V, (1, lens[i % len(lens)])).astype(np.int32)
            for i in range(n)]


def _cfgs(n):
    """Alternate greedy and seeded sampling: migration/replay must
    re-derive the PRNG key schedule, not just argmax."""
    return [dict(temperature=0.0, top_k=0, seed=0) if i % 2 == 0
            else dict(temperature=0.7, top_k=8, seed=11 + i)
            for i in range(n)]


def _engine_replicas(n, max_sessions=2, max_new=64):
    ek = {"max_sessions": max_sessions, "max_new_tokens": max_new}

    def factory(i):
        from benchmarks import fleet_factory

        return lambda: fleet_factory.create_lm(
            vocab=V, max_len=MAXLEN, device_index=i + 1)

    return [fleet.EngineReplica(f"r{i}", factory(i), engine_kwargs=ek)
            for i in range(n)]


def _wait_streams(replies, min_toks, timeout_s=60.0):
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        if all(len(r._stream) >= min_toks for r in replies):
            return
        time.sleep(0.002)
    raise AssertionError(
        [f"{r.session_id}: {len(r._stream)}" for r in replies])


# -- engine-level migration surface (export / resume) -----------------


def test_export_resume_kv_fast_path_bit_identity(lm):
    """Mid-stream export off engine A, resume on engine B with the
    KV slab transplanted: the resumed stream re-plays the ledger
    prefix then continues — full sequence bit-identical to
    generate(), greedy and sampled alike, and the 4-equation books
    balance ACROSS both engines (export nets against resume)."""
    NEW = 12
    prompts, cfgs = _prompts(2), _cfgs(2)
    want = [np.asarray(lm.generate(p, NEW, **c))
            for p, c in zip(prompts, cfgs)]
    d0 = stats.decode_stats().snapshot()
    a = serve.ServingEngine(lm, max_sessions=2,
                            max_new_tokens=NEW).start()
    replies = [a.submit_decode(p, NEW, **c)
               for p, c in zip(prompts, cfgs)]
    _wait_streams(replies, 3)
    ckpts = a.export_decode_sessions()
    assert len(ckpts) == 2
    for r in replies:  # local replies fail with the checkpoint
        with pytest.raises(serve.ServeMigratedError) as ei:
            r.result(timeout=10)
        assert ei.value.ckpt["kv"] is not None  # clean export: fast path
    a.stop()
    b = serve.ServingEngine(lm, max_sessions=2,
                            max_new_tokens=NEW).start()
    try:
        resumed = [b.resume_decode(c) for c in ckpts]
        for r, p, w in zip(resumed, prompts, want):
            got = np.asarray(r.result(timeout=60))
            np.testing.assert_array_equal(got, w)
            # the resumed stream carries the FULL token sequence:
            # ledger prefix replayed, then the continuation
            assert list(r.tokens(timeout=5)) == [
                int(t) for t in w[0, p.shape[1]:]]
    finally:
        b.stop()
    d1 = stats.decode_stats().snapshot()
    dd = {k: d1[k] - d0[k] for k in d1
          if isinstance(d1.get(k), (int, float))}
    assert dd["migrated"] == 2 and dd["resumed"] == 2
    assert dd["sessions"] == (dd["completed"] + dd["failed"]
                              + dd["expired"] + dd["shed"])


def test_resume_ledger_replay_path_bit_identity(lm):
    """Resume with the KV STRIPPED (the hung-dispatcher / SIGKILL
    shape): the target re-prefills prompt + ledger[:-1] and the
    stream is still bit-identical — correctness never rides on the
    checkpoint's KV."""
    NEW = 12
    prompts, cfgs = _prompts(2), _cfgs(2)
    want = [np.asarray(lm.generate(p, NEW, **c))
            for p, c in zip(prompts, cfgs)]
    a = serve.ServingEngine(lm, max_sessions=2,
                            max_new_tokens=NEW).start()
    replies = [a.submit_decode(p, NEW, **c)
               for p, c in zip(prompts, cfgs)]
    _wait_streams(replies, 4)
    ckpts = a.export_decode_sessions()
    a.stop()
    b = serve.ServingEngine(lm, max_sessions=2,
                            max_new_tokens=NEW).start()
    try:
        for c, w in zip(ckpts, want):
            c = dict(c, kv=None)
            got = np.asarray(b.resume_decode(c).result(timeout=60))
            np.testing.assert_array_equal(got, w)
    finally:
        b.stop()


def test_export_checkpoint_fields_and_deadline_remainder(lm):
    """The checkpoint is the portable migration contract: prompt +
    ledger + sampling config + seed + deadline REMAINDER (a migrated
    session must not get a fresh deadline) + KV rows; leaves are
    numpy/scalars/None so it crosses the CRC-framed IPC codec
    unchanged. An expired session is expired in place, not shipped."""
    NEW = 24
    p = _prompts(1)[0]
    a = serve.ServingEngine(lm, max_sessions=2,
                            max_new_tokens=NEW).start()
    try:
        r = a.submit_decode(p, NEW, temperature=0.7, top_k=8, seed=5,
                            deadline_ms=60000.0)
        _wait_streams([r], 2)
        ckpts = a.export_decode_sessions()
    finally:
        a.stop()
    (c,) = ckpts
    assert set(c) >= {"prompt", "toks", "n_new", "temperature",
                      "top_k", "seed", "deadline_ms_left", "kv"}
    np.testing.assert_array_equal(np.asarray(c["prompt"]), p)
    assert len(np.asarray(c["toks"]).ravel()) >= 2
    assert int(np.asarray(c["n_new"])) == NEW
    assert float(np.asarray(c["temperature"])) == 0.7
    assert int(np.asarray(c["seed"])) == 5
    assert 0 < float(np.asarray(c["deadline_ms_left"])) < 60000.0


def test_resume_sheds_when_full_like_submit(lm):
    """Admission control does not care where a session came from: a
    full pool sheds a resume with the same loud `ServeOverloadError`
    + retry hint, the checkpoint stays valid, and the resume lands
    once a slot frees."""
    NEW = 48  # long enough that the session is still in flight when
    #           exported — a 10-token session can finish inside the
    #           first pow2 run-ahead block before export() runs
    prompts = _prompts(3)
    want2 = np.asarray(lm.generate(prompts[2], NEW))
    a = serve.ServingEngine(lm, max_sessions=1,
                            max_new_tokens=NEW).start()
    r = a.submit_decode(prompts[2], NEW)
    _wait_streams([r], 2)
    ckpts = a.export_decode_sessions()
    assert ckpts, "session completed before export; raise NEW"
    a.stop()
    b = serve.ServingEngine(lm, max_sessions=1,
                            max_new_tokens=NEW).start()
    try:
        hold = b.submit_decode(prompts[0], NEW)
        with pytest.raises(serve.ServeOverloadError) as ei:
            b.resume_decode(ckpts[0])
        assert ei.value.retry_after_ms > 0
        hold.result(timeout=60)
        got = np.asarray(b.resume_decode(ckpts[0]).result(timeout=60))
        np.testing.assert_array_equal(got, want2)
    finally:
        b.stop()


# -- fleet-level: affinity, occupancy, migration, replay --------------


def test_occupancy_placement_and_full_fleet_shed(lm):
    """4 sessions over 2 replicas x 2 slots spread 2/2 by free-slot
    occupancy (not all onto the least-depth winner); the 5th sheds
    loudly with a retry hint — `retry_after_ms` stays the fleet's
    backpressure currency."""
    NEW = 30
    prompts, cfgs = _prompts(4), _cfgs(4)
    router = fleet.FleetRouter(_engine_replicas(2)).start()
    try:
        replies = [router.submit_decode(p, NEW, **c,
                                        session_id=f"s{i}")
                   for i, (p, c) in enumerate(zip(prompts, cfgs))]
        assert sorted(r.replica for r in replies) == \
            ["r0", "r0", "r1", "r1"]
        with pytest.raises(serve.ServeOverloadError) as ei:
            router.submit_decode(prompts[0], NEW, session_id="extra")
        assert ei.value.retry_after_ms > 0
        want = [np.asarray(lm.generate(p, NEW, **c))
                for p, c in zip(prompts, cfgs)]
        for r, w in zip(replies, want):
            np.testing.assert_array_equal(
                np.asarray(r.result(timeout=60)), w)
    finally:
        router.stop()


def test_drain_migrates_live_sessions_same_proxy(lm):
    """`drain(name)` mid-generation: every live session on the
    drained replica is checkpointed and resumed on the other one,
    the SAME `FleetDecodeReply` object keeps yielding (count-deduped
    ledger re-play — no tear, no duplicate), every stream is
    bit-identical, and the fleet-wide decode books balance exactly,
    `migrated`/`resumed` included."""
    NEW = 40
    prompts, cfgs = _prompts(4), _cfgs(4)
    want = [np.asarray(lm.generate(p, NEW, **c))
            for p, c in zip(prompts, cfgs)]
    s0 = stats.cache_stats()
    d0 = stats.decode_stats().snapshot()
    router = fleet.FleetRouter(_engine_replicas(2)).start()
    try:
        replies = [router.submit_decode(p, NEW, **c,
                                        session_id=f"d{i}")
                   for i, (p, c) in enumerate(zip(prompts, cfgs))]
        homes = [r.replica for r in replies]
        _wait_streams(replies, 2)
        router.drain("r0")
        moved = [r for r, h in zip(replies, homes) if h == "r0"]
        assert moved
        for i, r in enumerate(replies):
            got = np.asarray(r.result(timeout=120))
            np.testing.assert_array_equal(got, want[i])
            # the proxy's stream is the exact generated suffix
            assert list(r._stream) == [
                int(t) for t in want[i][0, prompts[i].shape[1]:]]
        for r in moved:
            assert r.replica == "r1"
            assert r.migrations == 1 and r.hops == 0
    finally:
        router.stop()
    s1 = stats.cache_stats()
    d1 = stats.decode_stats().snapshot()
    rep = fleet.reconcile(s0["serve"], s1["serve"], s0["fleet"],
                          s1["fleet"], decode0=d0, decode1=d1)
    assert rep["decode_router_terminals"], rep
    assert rep["decode_sessions"], rep
    assert rep["ok"], rep
    assert rep["decode_delta"]["migrated"] >= len(moved)
    assert (rep["decode_delta"]["migrated"]
            == rep["decode_delta"]["resumed"])


def test_session_affinity_sticky_routing(lm):
    """A session id that completed on a replica routes back to it
    while slots are free (sticky-by-session-id over least-depth);
    occupancy still wins when the sticky replica is full."""
    NEW = 6
    p = _prompts(1)[0]
    router = fleet.FleetRouter(_engine_replicas(2)).start()
    try:
        r = router.submit_decode(p, NEW, session_id="sticky")
        home = r.replica
        r.result(timeout=60)
        for _ in range(3):  # idle fleet: affinity decides every time
            r2 = router.submit_decode(p, NEW, session_id="sticky")
            assert r2.replica == home
            r2.result(timeout=60)
    finally:
        router.stop()


def test_kill_mid_stream_ledger_replay_bit_identity(lm):
    """A replica killed mid-generation (no checkpoint — the SIGKILL
    shape): the proxy re-prefills from its DELIVERED ledger on
    another replica and the final stream is still bit-identical;
    the hop is counted as a replay, not a planned migration."""
    NEW = 40
    prompts, cfgs = _prompts(2), _cfgs(2)
    want = [np.asarray(lm.generate(p, NEW, **c))
            for p, c in zip(prompts, cfgs)]
    router = fleet.FleetRouter(_engine_replicas(2),
                               max_failover_hops=2).start()
    try:
        k = [router.submit_decode(prompts[i], NEW, **cfgs[i],
                                  session_id=f"k{i}")
             for i in range(2)]
        _wait_streams(k, 2)
        victim = k[0].replica
        router.kill(victim)
        got = np.asarray(k[0].result(timeout=120))
        np.testing.assert_array_equal(got, want[0])
        assert list(k[0]._stream) == [
            int(t) for t in want[0][0, prompts[0].shape[1]:]]
        assert k[0].hops == 1 and k[0].replica != victim
        for i in range(2):
            np.testing.assert_array_equal(
                np.asarray(k[i].result(timeout=120)), want[i])
        time.sleep(0.3)  # supervisor settles the restart
    finally:
        router.stop()


def test_reconcile_decode_equation_fails_on_imbalance():
    """The decode-session equation is CHECKED, not decorative: a
    fabricated snapshot pair whose terminals don't cover the
    admissions flips `decode_sessions` — and the roll-up `ok` — to
    False."""
    s = stats.cache_stats()
    zero = {k: 0 for k in ("sessions", "completed", "failed",
                           "expired", "shed", "migrated", "resumed")}
    bad = dict(zero, sessions=3, completed=2)  # 1 session vanished
    rep = fleet.reconcile(s["serve"], s["serve"], s["fleet"],
                          s["fleet"], decode0=zero, decode1=bad)
    assert rep["decode_sessions"] is False
    assert rep["ok"] is False
    good = dict(zero, sessions=3, completed=2, failed=1)
    rep = fleet.reconcile(s["serve"], s["serve"], s["fleet"],
                          s["fleet"], decode0=zero, decode1=good)
    assert rep["decode_sessions"] is True


def test_warm_decode_fleet_wide(lm):
    """`FleetRouter.warm_decode` fans the dispatch-ladder warmup to
    every in-rotation replica and sums the executables — traffic
    never pays first-rung compiles."""
    router = fleet.FleetRouter(_engine_replicas(2)).start()
    try:
        n = router.warm_decode([2, 3], 8)
        assert n >= 2  # at least one executable per replica
    finally:
        router.stop()


# -- tooling satellite: decode saturation in serve_health ------------


def test_serve_health_renders_decode_saturation(tmp_path):
    """A health snapshot carrying the decode occupancy block renders
    a `decode[...]` bracket (the same numbers the router's placement
    reads from heartbeats); a pre-P25 snapshot WITHOUT the block
    renders byte-identically to before — the probe contract is
    append-only."""
    import importlib.util
    import json

    spec_ = importlib.util.spec_from_file_location(
        "serve_health_for_decode_test",
        os.path.join(_ROOT, "tools", "serve_health.py"))
    sh = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(sh)
    base = {"state": "ready", "pid": 123, "queue_depth": 0, "shed": 2}
    old = tmp_path / "old.health.json"
    old.write_text(json.dumps(base))
    code_old, line_old = sh.probe(str(old))
    assert code_old == 0 and "decode[" not in line_old
    new = tmp_path / "new.health.json"
    new.write_text(json.dumps(dict(base, decode={
        "active_sessions": 3, "free_slots": 1,
        "tokens_per_s": 41.5})))
    code_new, line_new = sh.probe(str(new))
    assert code_new == 0
    assert "decode[sessions=3 free_slots=1 tok/s=41.5]" in line_new
    # append-only: stripping the bracket recovers the old line
    assert line_new.startswith(line_old)


# -- proc transport: the wire + REAL SIGKILLs -------------------------


def _lm_spec(tmp_store=None, max_sessions=2, max_new=64):
    s = {"factory": "benchmarks.fleet_factory:create_lm",
         "factory_kwargs": {"vocab": V, "max_len": MAXLEN},
         "sys_path": [_ROOT],
         "engine": {"max_sessions": max_sessions,
                    "max_new_tokens": max_new},
         "warm_decode": {"prompt_lens": [2, 3, 5, 4],
                         "max_new_tokens": 16}}
    if tmp_store:
        s["export_cache"] = str(tmp_store)
    return s


def _proc_replicas(n, spec):
    return fleet.make_replicas(n, spec, transport="proc",
                               name_prefix="w",
                               heartbeat_interval_s=0.1,
                               spawn_timeout_s=120.0)


def test_proc_decode_drain_migration_and_sigkill_replay(lm, tmp_path):
    """The tier-1 proc smoke, one worker pair end to end: decode
    warmup over the wire, occupancy placement across processes,
    `drain` shipping LIVE KV slabs over the CRC-framed IPC
    (MIGRATE/RESUME frames) with the same proxy still yielding, a
    REAL SIGKILL mid-generation replayed from the delivered ledger,
    a respawned worker whose spec'd `warm_decode` is DESERIALIZE-only
    from the shared store (worker-side counters over the wire:
    export hits >= 1, traces == 0), and `fleet.reconcile` exact
    across the process boundary — transport ledger included.
    The `-m slow` chaos soak scales the same path up."""
    NEW = 40
    store = tmp_path / "store"
    device.set_export_cache(str(store))
    prompts, cfgs = _prompts(4), _cfgs(4)
    want = [np.asarray(lm.generate(p, NEW, **c))
            for p, c in zip(prompts, cfgs)]
    s0 = stats.cache_stats()
    d0 = stats.decode_stats().snapshot()
    reps = _proc_replicas(2, _lm_spec())
    # w0 is PACED (every decode dispatch sleeps 0.5 s, respawns
    # included): a toy stream otherwise finishes in milliseconds, and
    # "drained / killed MID-generation" is a race the test loses. (It
    # used to win it only because the live path recompiled every
    # warmed executable — PR 21.) w1 runs free, so its own two
    # sessions are done — its slots free — when w0's arrive.
    reps[0].spec["injector"] = {"schedule": {"decode_hang": 1.0},
                                "hang_s": 0.5}
    router = fleet.FleetRouter(reps, max_failover_hops=2).start()
    try:
        assert router.warm_decode([2, 3, 5, 4], NEW + 8) >= 2

        # occupancy placement across REAL processes, then drain w0:
        # its live sessions cross the wire and keep streaming
        replies = [router.submit_decode(p, NEW, **c,
                                        session_id=f"s{i}")
                   for i, (p, c) in enumerate(zip(prompts, cfgs))]
        assert sorted(r.replica for r in replies) == \
            ["w0", "w0", "w1", "w1"]
        _wait_streams([r for r in replies if r.replica == "w0"], 3)
        _wait_streams([r for r in replies if r.replica == "w1"], NEW)
        router.drain("w0")
        for i, r in enumerate(replies):
            got = np.asarray(r.result(timeout=180))
            np.testing.assert_array_equal(got, want[i])
            assert list(r._stream) == [
                int(t) for t in want[i][0, prompts[i].shape[1]:]]
        assert sum(r.migrations for r in replies) >= 1
        assert all(r.replica == "w1"
                   for r in replies if r.migrations)

        # REAL SIGKILL mid-generation: ledger replay, bit-identical
        router.rejoin("w0")
        k = [router.submit_decode(prompts[i], NEW, **cfgs[i],
                                  session_id=f"k{i}")
             for i in range(2)]
        live = next(s for s in k if s.replica == "w0")  # the paced one
        _wait_streams([live], 3)
        victim = live.replica
        by_name = {r.name: r for r in reps}
        by_name[victim].sigkill()  # discovered, not told
        for i in range(2):
            got = np.asarray(k[i].result(timeout=180))
            np.testing.assert_array_equal(got, want[i])
            assert list(k[i]._stream) == [
                int(t) for t in want[i][0, prompts[i].shape[1]:]]
        assert live.hops >= 1 and live.replica != victim

        # the respawned generation re-ran warm_decode from the spec,
        # deserialize-only from the store gen-0 populated — probed
        # over the wire via the live `counters` CTRL op (the BYE
        # handshake only lands once a generation EXITS)
        deadline = time.perf_counter() + 60
        exp = None
        while time.perf_counter() < deadline:
            try:
                exp = by_name[victim].counters().get("export")
            except (serve.ServeClosedError,
                    serve.ServeDispatchError):
                exp = None  # still respawning
            if exp and exp.get("hits", 0) >= 1:
                break
            time.sleep(0.25)
        assert exp is not None, "respawned worker never answered"
        assert exp.get("hits", 0) >= 1, exp
        assert exp.get("traces", 0) == 0, exp
        time.sleep(0.5)
    finally:
        router.stop()
    s1 = stats.cache_stats()
    d1 = stats.decode_stats().snapshot()
    rep = fleet.reconcile(s0["serve"], s1["serve"], s0["fleet"],
                          s1["fleet"], replicas=reps,
                          decode0=d0, decode1=d1)
    assert rep["decode_router_terminals"], rep
    assert rep["decode_sessions"], rep
    assert rep["transport"], rep
    assert rep["ok"], rep


@pytest.mark.slow
def test_proc_decode_chaos_soak_full(lm, tmp_path):
    """Full chaos soak (`-m slow`): a steady session load over 2
    worker processes with >= 2 pinned REAL SIGKILLs mid-generation.
    Every DELIVERED stream bit-identical, every failure loud and
    counted, zero torn/duplicated tokens (the proxy's prefix guard
    raises on a tear — the test would ERROR, not just fail), and the
    fleet-wide decode + transport reconciliation exact at
    quiescence. The kills are DIRECT `os.kill(pid, SIGKILL)`s pinned
    mid-wave (the injector's scheduled steps are consumed by shed
    retries once capacity halves, which made the second kill racy);
    the evidence is still DISCOVERED from worker exit codes, never
    trusted from the killer."""
    NEW = 24
    N = 12
    store = tmp_path / "store"
    device.set_export_cache(str(store))
    prompts, cfgs = _prompts(N), _cfgs(N)
    want = [np.asarray(lm.generate(p, NEW, **c))
            for p, c in zip(prompts, cfgs)]
    s0 = stats.cache_stats()
    d0 = stats.decode_stats().snapshot()
    reps = _proc_replicas(2, _lm_spec())
    by_name = {r.name: r for r in reps}
    router = fleet.FleetRouter(
        reps, max_failover_hops=3,
        max_shed_retries=6, max_shed_sleep_s=0.5,
        max_restarts=100, supervise_interval_s=0.01, seed=7).start()
    delivered = failed = refused = kill_done = 0
    try:
        router.warm_decode([2, 3, 5, 4], NEW + 8)
        replies = []
        for i, (p, c) in enumerate(zip(prompts, cfgs)):
            for _ in range(40):
                try:
                    replies.append(
                        (i, router.submit_decode(
                            p, NEW, **c, session_id=f"c{i}")))
                    break
                except serve.ServeOverloadError as e:
                    time.sleep(max(e.retry_after_ms, 1.0) / 1e3)
                except fleet.FleetUnavailableError:
                    time.sleep(0.1)
            else:
                refused += 1
            # two pinned REAL SIGKILLs mid-generation, one per wave,
            # each against the replica streaming the freshest session
            if kill_done * 5 + 4 <= len(replies) and kill_done < 2:
                r = replies[-1][1]
                _wait_streams([r], 2)
                victim = r.replica
                if victim in by_name:
                    by_name[victim].sigkill()
                    kill_done += 1
        for i, r in replies:
            try:
                got = np.asarray(r.result(timeout=180))
            except (serve.ServeDispatchError, serve.ServeDeadlineError,
                    serve.ServeClosedError, serve.ServeOverloadError,
                    fleet.FleetUnavailableError):
                failed += 1
                continue
            np.testing.assert_array_equal(got, want[i])
            delivered += 1
        time.sleep(1.0)  # respawns settle
    finally:
        router.stop()
    kills = sum(
        1 for r in reps
        for g in r.transport_snapshot()["generations"].values()
        if g.get("exit_code") == -9)
    assert kills >= 2, kills
    assert delivered >= N // 2, (delivered, failed, refused)
    assert delivered + failed + refused == N
    s1 = stats.cache_stats()
    d1 = stats.decode_stats().snapshot()
    rep = fleet.reconcile(s0["serve"], s1["serve"], s0["fleet"],
                          s1["fleet"], replicas=reps,
                          decode0=d0, decode1=d1)
    assert rep["decode_router_terminals"], rep
    assert rep["decode_sessions"], rep
    assert rep["ok"], rep
