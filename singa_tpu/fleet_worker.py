"""Fleet worker entrypoint (ISSUE 13, TCP modes ISSUE 18): one
serving replica in its own process, speaking the framed protocol of
`singa_tpu.fleet_proc` over a socket. Three launch shapes:

  * **spawn** (no CLI args): `ProcReplica(mode="spawn")` launched us
    with the replica spec in `SINGA_TPU_FLEET_SPEC` and a loopback
    port to dial — today's single-host behavior, unchanged. Socket
    EOF means the parent died: exit, no orphans.
  * **--connect HOST:PORT --token T [--name N]**: the multi-host
    launch recipe. The worker dials the parent's listener (or its
    ChaosProxy front door), authenticates with HELLO {token, fence,
    need_spec}, and receives WELCOME — which SHIPS the replica spec
    when the worker has none in its env (a remote host needs only
    this CLI plus the prewarmed export store). A lost connection is
    NOT death out here: the worker re-dials with seeded backoff
    inside the parent's advertised `reconnect_window_s`, echoing the
    generation fence from its WELCOME; the parent resumes the same
    generation (seqs reset per connection) or answers FENCED — the
    loud "you are superseded" verdict — and the worker exits.
  * **--listen HOST:PORT --token T [--name N]**: an already-running
    worker that a `ProcReplica(mode="connect")` parent dials. The
    worker accepts one parent at a time; the worker still speaks
    HELLO first. A FENCED verdict here resets the fence so the next
    parent (re)dial adopts the worker FRESH — a superseded fence is
    dead, never resurrected.

The worker

  1. forces the jax platform the parent named (`JAX_PLATFORMS` —
     tier-1 hermeticity: a CPU-pinned test suite must never have a
     worker wander onto an accelerator),
  2. arms the SHARED export-cache store (populate-once-start-N: with
     `tools/prewarm.py` run once, this boot — and every respawn after
     a SIGKILL — is deserialize-only, export hits >= 1, traces == 0),
  3. builds the model from the spec's deterministic factory
     ("module:callable", the `tools/prewarm.py --factory` idiom) and
     runs a `ServingEngine` over it,
  4. serves the framed request/reply protocol: REQ -> sync ACK
     (admission verdicts keep their exact single-engine error types)
     -> REP/ERR per request; HB heartbeats carry the engine `health()`
     snapshot plus the terminal/export counters the parent's
     reconciliation and deserialize-only pins read; a DRAIN control
     ships the final counters (BYE) — the end-of-run reconciliation
     handshake — before a clean exit 0.

Every frame out carries a per-connection monotonic sequence number
(wire v2) and every frame in is checked (`FrameReader(check_seq=
True)`): duplication or reordering on the path is a typed error, not
data. Sends go through the partial-write-hardened `send_frame` loop
under one lock — two threads can never interleave bytes mid-frame.

The worker never writes to stdout (the parent may be a bench stage
whose stdout is a JSON contract); logs go to stderr."""
from __future__ import annotations

import argparse
import json
import os
import struct
import sys
import threading
import time

import numpy as np


def _log(msg: str) -> None:
    print(f"[fleet-worker {os.getpid()}] {msg}", file=sys.stderr,
          flush=True)


class _Fenced(RuntimeError):
    """The parent answered FENCED: this worker's generation (or its
    fresh-boot claim) is refused. Not retryable on the same fence."""


def _parse_addr(s: str):
    host, _, port = s.rpartition(":")
    return (host or "127.0.0.1", int(port))


def _parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="python -m singa_tpu.fleet_worker",
        description="fleet serving worker (spawned by ProcReplica, "
                    "or launched on any host with --connect)")
    ap.add_argument("--connect", metavar="HOST:PORT", default=None,
                    help="dial a ProcReplica(mode='listen') parent")
    ap.add_argument("--listen", metavar="HOST:PORT", default=None,
                    help="accept a ProcReplica(mode='connect') parent")
    ap.add_argument("--token", default=None,
                    help="shared auth token (HELLO is refused "
                         "without it)")
    ap.add_argument("--name", default=None,
                    help="replica name for logs/heartbeats")
    args = ap.parse_args(argv)
    if args.connect and args.listen:
        ap.error("--connect and --listen are mutually exclusive")
    if (args.connect or args.listen) and not args.token:
        ap.error("--token is required with --connect/--listen")
    return args


def main(argv=None) -> int:
    args = _parse_args(argv)
    mode = ("connect" if args.connect
            else "listen" if args.listen else "spawn")
    raw = os.environ.get("SINGA_TPU_FLEET_SPEC")
    if mode == "spawn" and not raw:
        raise SystemExit(
            "fleet_worker: SINGA_TPU_FLEET_SPEC is not set — this "
            "module is spawned by singa_tpu.fleet_proc.ProcReplica; "
            "to run it by hand use --connect HOST:PORT --token ...")
    spec = json.loads(raw) if raw else None

    # The parent names the platform through JAX_PLATFORMS (tier-1
    # pins cpu); jax reads it itself when the first backend is built.
    import jax

    from singa_tpu import device, resilience, serve, stats
    from singa_tpu import fleet_proc as wire
    from singa_tpu import slo as slo_mod
    from singa_tpu import trace as trace_mod

    import socket

    token = args.token if args.token is not None \
        else (spec or {}).get("token")
    name = args.name or (spec or {}).get("name", "worker")
    tcp = mode != "spawn"

    # -- connection state: one link, many connection epochs ---------------
    # All sends funnel through `link_send` under ONE lock: the frame
    # gets this connection's next sequence number and goes out via the
    # partial-write-hardened `wire.send_frame` loop. A send failure
    # poisons the connection (bytes may be half out — it can never
    # carry another frame); in tcp mode the serve loop then runs the
    # re-adoption machinery instead of exiting.
    wlock = threading.Lock()
    link = {"sock": None, "tx_seq": 0}
    state = {"fence": None, "window_s": 10.0, "fenced_streak": 0}

    def link_attach(s, tx_seq=0):
        with wlock:
            link["sock"] = s
            link["tx_seq"] = tx_seq

    def link_detach(s=None):
        with wlock:
            if s is None or link["sock"] is s:
                link["sock"] = None

    def link_send(ftype, rid, payload, corrupt=False):
        with wlock:
            s = link["sock"]
            if s is None:
                raise OSError("link down (reconnecting)")
            frame = wire.encode_frame(ftype, rid, payload,
                                      corrupt=corrupt,
                                      seq=link["tx_seq"])
            try:
                wire.send_frame(s, frame, deadline_s=10.0)
            except OSError:
                link["sock"] = None
                try:
                    s.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                raise
            link["tx_seq"] += 1

    def handshake(conn, need_spec, deadline_s=30.0):
        """HELLO -> WELCOME/FENCED on a fresh connection. The worker
        speaks first; its HELLO is the connection's frame seq 0, so
        after a WELCOME the link attaches at tx_seq=1. Frames
        coalesced behind the WELCOME come back for serve-loop
        replay."""
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        rd = wire.FrameReader(check_seq=True)
        hello = {"token": token, "pid": os.getpid(), "name": name,
                 "fence": state["fence"], "need_spec": bool(need_spec)}
        wire.send_frame(conn, wire.encode_frame(
            wire.HELLO, 0, json.dumps(hello).encode("utf-8"), seq=0),
            deadline_s=min(10.0, deadline_s))
        conn.settimeout(0.2)
        deadline = time.perf_counter() + deadline_s
        welcome, stash = None, []
        while welcome is None:
            if time.perf_counter() > deadline:
                raise OSError(f"no WELCOME within {deadline_s:g}s")
            try:
                chunk = conn.recv(1 << 16)
            except socket.timeout:
                continue
            if not chunk:
                raise OSError("connection closed before WELCOME")
            for ftype, rid, payload in rd.feed(chunk):
                if ftype == wire.FENCED:
                    try:
                        reason = json.loads(
                            payload.decode("utf-8")).get("reason")
                    except Exception:
                        reason = "?"
                    raise _Fenced(str(reason))
                if ftype == wire.WELCOME and welcome is None:
                    welcome = json.loads(payload.decode("utf-8"))
                else:
                    stash.append((ftype, rid, payload))
        state["fence"] = welcome.get("fence")
        state["window_s"] = float(
            welcome.get("reconnect_window_s", state["window_s"]))
        state["fenced_streak"] = 0
        return welcome, rd, stash

    lsock = None
    if mode == "listen":
        lhost, lport = _parse_addr(args.listen)
        lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lsock.bind((lhost, lport))
        lsock.listen(1)
        lsock.settimeout(1.0)
        _log(f"{name}: listening on "
             f"{lsock.getsockname()[0]}:{lsock.getsockname()[1]}")

    def accept_parent():
        """listen mode: wait for a parent to dial and authenticate.
        A FENCED verdict resets the fence — the next adoption is
        FRESH by construction — and keeps waiting (bounded streak)."""
        while True:
            try:
                conn, _ = lsock.accept()
            except socket.timeout:
                continue
            except OSError:
                return None  # listener closed
            try:
                return (conn,) + handshake(
                    conn, need_spec=spec is None, deadline_s=15.0)
            except _Fenced as e:
                _log(f"{name}: FENCED ({e}); fence reset — next "
                     "adoption is fresh")
                state["fence"] = None
                state["fenced_streak"] += 1
                try:
                    conn.close()
                except OSError:
                    pass
                if state["fenced_streak"] >= 8:
                    _log(f"{name}: fenced {state['fenced_streak']}x "
                         "in a row; giving up")
                    return None
            except (OSError, wire.FrameCorruptError) as e:
                # a corrupt/reordered handshake frame (a chaotic
                # network CAN mangle the WELCOME) drops the dial, not
                # the worker — the parent redials
                _log(f"{name}: handshake failed ({e}); waiting")
                try:
                    conn.close()
                except OSError:
                    pass

    def redial():
        """connect mode: bounded seeded-backoff re-dial echoing the
        stored generation fence. FENCED => superseded => give up."""
        deadline = time.perf_counter() + state["window_s"] + 5.0
        attempt = 0
        while True:
            left = deadline - time.perf_counter()
            if left <= 0:
                _log(f"{name}: redial window "
                     f"({state['window_s']:g}s) exhausted")
                return None
            time.sleep(min(left, resilience.backoff_delay_s(
                attempt, 0.05, seed=os.getpid() & 0x7FFFFFFF,
                salt="redial")))
            attempt += 1
            try:
                s = socket.create_connection(dial_addr, timeout=5.0)
            except OSError:
                continue
            try:
                return (s,) + handshake(s, need_spec=False,
                                        deadline_s=10.0)
            except _Fenced as e:
                _log(f"{name}: reconnect FENCED ({e}); exiting")
                try:
                    s.close()
                except OSError:
                    pass
                return None
            except (OSError, wire.FrameCorruptError):
                # timeout, reset, or a WELCOME mangled in transit:
                # close and redial — only FENCED ends the attempt loop
                try:
                    s.close()
                except OSError:
                    pass

    # -- first connection (tcp) / spec resolution -------------------------
    stash0: list = []
    welcome = None
    if mode == "connect":
        dial_addr = _parse_addr(args.connect)
        # bounded retries: the first WELCOME can be mangled in transit
        # on a chaotic network just like any later one
        for boot_attempt in range(5):
            try:
                sock = socket.create_connection(dial_addr,
                                                timeout=30.0)
            except OSError as e:
                raise SystemExit(
                    f"fleet_worker: cannot dial parent at "
                    f"{dial_addr[0]}:{dial_addr[1]} ({e})")
            try:
                welcome, reader, stash0 = handshake(
                    sock, need_spec=spec is None)
                break
            except _Fenced as e:
                raise SystemExit(
                    f"fleet_worker: refused by parent (FENCED: {e})")
            except (OSError, wire.FrameCorruptError) as e:
                try:
                    sock.close()
                except OSError:
                    pass
                if boot_attempt == 4:
                    raise SystemExit(
                        f"fleet_worker: handshake never completed "
                        f"({e})")
                _log(f"{name}: boot handshake failed ({e}); "
                     "redialing")
                time.sleep(0.1 * (boot_attempt + 1))
    elif mode == "listen":
        got = accept_parent()
        if got is None:
            raise SystemExit("fleet_worker: no parent adopted us")
        sock, welcome, reader, stash0 = got
    if tcp and spec is None:
        spec = welcome.get("spec")
        if spec is None:
            raise SystemExit(
                "fleet_worker: no spec in env and the parent's "
                "WELCOME shipped none")
    spec.setdefault("name", name)
    name = spec["name"]

    # -- engine boot (shared by all modes) --------------------------------
    if spec.get("export_cache"):
        device.set_export_cache(spec["export_cache"])
    if spec.get("buckets"):
        device.set_shape_buckets(**spec["buckets"])
    if spec.get("quant"):
        # int8 inference (ISSUE 19): armed BEFORE the model/engine
        # build so the slab, the warmed ladder, and the AOT keys all
        # agree — every replica of a fleet must share the mode or
        # MIGRATE frames would cross quant forms (import_slab_rows
        # refuses loudly and the session demotes to replay)
        device.set_inference_quant(spec["quant"])

    def arm_tracing(ship_capacity=2048, ring_capacity=None):
        """Worker tracer + span ship-back: completed spans carrying a
        trace context are drained (bounded per frame) onto REP/HB/BYE
        frames for the parent's merged timeline. Overflow of the
        bounded ship buffer drops oldest, counted — frames never grow
        unboundedly."""
        trace_mod.configure(enabled=True,
                            ship_capacity=int(ship_capacity),
                            ring_capacity=ring_capacity)

    tr_spec = spec.get("trace") or {}
    if tr_spec.get("enabled"):
        arm_tracing(tr_spec.get("ship_capacity", 2048),
                    tr_spec.get("ring_capacity"))
    slo_spec = spec.get("slo") or {}
    if slo_spec.get("enabled"):
        # ISSUE 20: arm the worker's local SLO sketches from the
        # router's spec so the whole fleet samples under ONE spec;
        # workers never write alerts (the router holds the merged
        # view and the alerting state) — alerts_path stays None here
        slo_mod.configure(**dict(slo_spec, alerts_path=None))

    factory = wire.resolve_factory(spec)
    t0 = time.perf_counter()
    model = factory(**(spec.get("factory_kwargs") or {}))
    _log(f"{name}: model built in {time.perf_counter() - t0:.2f}s "
         f"(platform {jax.default_backend()}, mode {mode})")

    injector = None
    if spec.get("injector"):
        ij = spec["injector"]
        injector = resilience.FaultInjector(
            seed=int(ij.get("seed", 0)),
            schedule=ij.get("schedule") or {},
            hang_s=float(ij.get("hang_s", 0.05)))
    metrics = None
    if spec.get("metrics_path"):
        metrics = trace_mod.MetricsLogger(spec["metrics_path"])
    engine = serve.ServingEngine(model, fault_injector=injector,
                                 metrics=metrics,
                                 **(spec.get("engine") or {}))
    engine.start()
    if spec.get("warm_decode"):
        # decode-tier AOT warmup at boot (and at every RESPAWN —
        # restart() reuses this spec): with the shared store prewarmed
        # this is deserialize-only, so a respawned replica re-enters
        # the decode rotation without paying a compile
        wd = spec["warm_decode"]
        t0 = time.perf_counter()
        n = engine.warm_decode(wd.get("prompt_lens") or (),
                               wd.get("max_new_tokens"),
                               samplers=wd.get("samplers") or ())
        _log(f"{name}: decode tier warmed ({n} executables, "
             f"{time.perf_counter() - t0:.2f}s)")

    if mode == "spawn":
        sock = socket.create_connection(
            ("127.0.0.1", int(spec["port"])), timeout=30.0)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        reader = wire.FrameReader(check_seq=True)
        link_attach(sock, tx_seq=0)
    else:
        link_attach(sock, tx_seq=1)  # HELLO was this link's seq 0

    tear_next = threading.Event()  # torn_frame chaos: corrupt next REP
    stop_ev = threading.Event()
    outbox_lock = threading.Lock()
    flush_lock = threading.Lock()  # serializes waiter vs drain flush
    outbox = []  # [(rid, ServeReply)] admitted, awaiting resolution

    def send(ftype, rid, payload, rep_frame=False):
        corrupt = rep_frame and tear_next.is_set()
        if corrupt:
            tear_next.clear()
        link_send(ftype, rid, payload, corrupt=corrupt)

    def counters_payload():
        s = stats.cache_stats()
        d = s["decode"]
        out = {
            "terminal": serve.terminal_counters(),
            "poisoned": s["serve"]["poisoned"],
            "late": s["serve"]["late"],
            "export": {"hits": s["export"]["hits"],
                       "traces": s["export"]["traces"],
                       "misses": s["export"]["misses"]},
            # decode-session books (ISSUE 17): the worker side of the
            # fleet-wide 4-equation reconciliation — sessions ==
            # completed + failed + expired + shed, with migrated/
            # resumed tracking the sessions that crossed replicas
            "decode": {k: int(d.get(k, 0)) for k in (
                "sessions", "completed", "failed", "expired", "shed",
                "migrated", "resumed", "tokens_streamed", "prefills",
                "decode_steps", "slots", "slots_in_use")},
            "pid": os.getpid(),
        }
        if trace_mod.enabled():
            t = s["trace"]
            out["trace"] = {"spans": t["spans"],
                            "shipped": t["shipped"],
                            "ship_dropped": t["ship_dropped"]}
        # ISSUE 20: cumulative sketch payload — the key exists ONLY
        # while the SLO engine is armed (byte-absence, PR 15
        # discipline); cumulative-replace makes ingest idempotent
        # under heartbeat loss, duplication, and reconnect
        s_payload = slo_mod.wire_payload()
        if s_payload is not None:
            out["slo"] = s_payload
        return out

    def send_hb():
        snap = engine.health()
        snap["time"] = round(time.time(), 3)
        snap["name"] = name
        hb = counters_payload()
        hb["health"] = snap
        hb["retry_after_ms"] = engine._estimate_retry_after_ms(
            engine._depth)
        if trace_mod.enabled():
            # (wall, mono) pair: the parent's fallback clock-offset
            # estimate; completed trace-stamped spans piggyback here
            # so even a request-quiet worker keeps shipping. Both
            # keys exist ONLY while tracing is armed — a disabled
            # fleet's heartbeats are byte-identical to pre-trace.
            hb["clock"] = {"mono": time.perf_counter(),
                           "wall": time.time()}
            spans = trace_mod.drain_shipped(wire.SPANS_PER_HB)
            if spans:
                hb["spans"] = spans
        send(wire.HB, 0, json.dumps(hb, default=str).encode("utf-8"))

    def heartbeat_loop():
        interval = float(spec.get("heartbeat_interval_s", 0.25))
        while not stop_ev.wait(interval):
            try:
                send_hb()
            except OSError:
                if not tcp:
                    return
                # tcp: link down mid-reconnect — keep ticking; the
                # first beat after re-adoption lands on the new
                # connection (a resumed worker must re-enter the
                # rotation fresh, not stale)

    def flush_done(block_all: bool = False) -> None:
        """Send REP/ERR for every resolved future in the outbox;
        `block_all` waits every future out (the drain path — the
        reconciliation handshake must account for them all).
        `flush_lock` keeps the waiter thread and the drain path from
        double-sending one request's frame. A send failure (link
        down) leaves the item IN the outbox: it is resent on the next
        connection, where the parent — which swept the rid into
        failover when the connection died — drops it by rid. Never
        lost, never double-delivered."""
        with flush_lock:
            while True:
                with outbox_lock:
                    items = list(outbox)
                if not items:
                    return
                progressed = False
                for rid, reply in items:
                    if not reply.done():
                        if block_all:
                            try:
                                reply.result(30.0)
                            except BaseException:
                                pass
                        else:
                            continue
                    try:
                        val = reply.result(0.0)
                        flags = 1 if reply.deadline_exceeded else 0
                        # piggyback trace spans ONLY under ship-buffer
                        # pressure (heartbeats are the steady-state
                        # carrier — span bytes here are request-path
                        # latency); an untraced run drains nothing and
                        # the flag bit stays 0 — byte-identical to the
                        # pre-trace REP layout
                        pending, cap = trace_mod.ship_backlog()
                        spans = (trace_mod.drain_shipped(
                            wire.SPANS_PER_REP)
                            if cap and pending >= cap // 2 else [])
                        if spans:
                            flags |= 2
                        payload = bytes([flags])
                        payload += wire.encode_tree(val)
                        if spans:
                            sb = json.dumps(spans, default=str).encode("utf-8")
                            payload += struct.pack(">I", len(sb)) + sb
                        send(wire.REP, rid, payload, rep_frame=True)
                    except OSError:
                        raise
                    except BaseException as e:  # noqa: BLE001 — wire
                        send(wire.ERR, rid, json.dumps(
                            wire.encode_error(e)).encode("utf-8"))
                    with outbox_lock:
                        outbox.remove((rid, reply))
                    progressed = True
                if not block_all:
                    return
                if not progressed:
                    time.sleep(0.005)

    def waiter_loop():
        while not stop_ev.is_set():
            try:
                flush_done()
            except OSError:
                if not tcp:
                    return
            time.sleep(0.001)

    # -- decode tier (ISSUE 17) -------------------------------------------
    # One streamer thread per admitted session: every generated token
    # rides a TOK frame as its fused step lands, and the terminal is
    # exactly ONE of REP (completed — the full [1, P+n] array, the
    # bit-identity surface), ERR (failed/expired), or MIGRATE (the
    # session left with the drain checkpoint; supersedes ERR — a
    # migrated session has no local terminal, it re-admits elsewhere).
    decode_threads = []

    def stream_decode(rid, reply):
        try:
            try:
                for tok in reply.tokens():
                    send(wire.TOK, rid, struct.pack(">i", int(tok)))
            except serve.ServeMigratedError as e:
                send(wire.MIGRATE, rid, wire.encode_tree(e.ckpt))
                return
            except BaseException as e:  # noqa: BLE001 — wire
                send(wire.ERR, rid, json.dumps(
                    wire.encode_error(e)).encode("utf-8"))
                return
            val = reply.result(0.0)
            flags = 1 if reply.deadline_exceeded else 0
            send(wire.REP, rid, bytes([flags]) + wire.encode_tree(val),
                 rep_frame=True)
        except OSError:
            pass  # connection gone: the parent swept this session
            # into failover (or its death sweep owns the books); a
            # late terminal on a later connection would be dropped
            # by rid anyway

    def admit_decode(rid, admit, tid, parent):
        """Shared DECODE/RESUME admission: sync ACK (exact engine
        error types on refusal, the REQ contract) then a streamer
        thread owns the session's frames."""
        if tid is not None and not trace_mod.enabled():
            arm_tracing()
        try:
            with trace_mod.context(tid, parent):
                reply = admit()
        except BaseException as e:  # noqa: BLE001 — wire
            send(wire.ERR, rid, json.dumps(
                wire.encode_error(e)).encode("utf-8"))
            return
        send(wire.ACK, rid,
             b"" if tid is None
             else struct.pack(">d", time.perf_counter()))
        t = threading.Thread(target=stream_decode, args=(rid, reply),
                             daemon=True)
        decode_threads.append(t)
        t.start()

    def handle_ctrl(rid, msg):
        op = msg.get("op")
        if op == "drain":
            return "drain", bool(msg.get("drain", True))
        if op == "counters":
            send(wire.CTRL_OK, rid,
                 json.dumps(counters_payload()).encode("utf-8"))
        elif op == "warm_decode":
            try:
                warmed = engine.warm_decode(
                    msg.get("prompt_lens") or (),
                    msg.get("max_new_tokens"),
                    samplers=msg.get("samplers") or ())
                send(wire.CTRL_OK, rid, json.dumps(
                    {"warmed": warmed}).encode("utf-8"))
            except BaseException as e:  # noqa: BLE001 — wire
                send(wire.ERR, rid, json.dumps(
                    wire.encode_error(e)).encode("utf-8"))
        elif op == "hang_once":
            hang_s = float(msg.get("s", 0.05))
            orig = engine._chaos_attempt
            fired = []

            def hooked(group):
                if not fired:
                    fired.append(1)
                    engine._chaos_attempt = orig
                    time.sleep(hang_s)
                return orig(group)

            engine._chaos_attempt = hooked
        elif op == "torn_frame":
            tear_next.set()
        return None, None

    def dispatch(ftype, rid, payload):
        """One inbound frame => engine action. Returns the drain mode
        when a DRAIN control arrives, else None."""
        if ftype == wire.REQ:
            dl, arrays, tid, parent = \
                wire.decode_req_payload(payload)
            if tid is not None and not trace_mod.enabled():
                # parent enabled tracing after this worker
                # spawned: a traced REQ arms it lazily
                arm_tracing()
            try:
                with trace_mod.context(tid, parent):
                    reply = engine.submit(*arrays, deadline_ms=dl)
            except BaseException as e:  # noqa: BLE001
                send(wire.ERR, rid, json.dumps(
                    wire.encode_error(e)).encode("utf-8"))
                return None
            # ACK strictly before the outbox registration:
            # the waiter can then never put a REP on the wire
            # ahead of its ACK. A TRACED request's ACK carries
            # the worker perf_counter stamp (8 bytes) the
            # parent's clock-offset estimate reads; an
            # untraced ACK stays empty — zero added bytes.
            send(wire.ACK, rid,
                 b"" if tid is None
                 else struct.pack(">d", time.perf_counter()))
            with outbox_lock:
                outbox.append((rid, reply))
        elif ftype == wire.DECODE:
            d, tid, parent = wire.decode_decode_payload(payload)
            dl = d.get("deadline_ms")
            admit_decode(rid, lambda: engine.submit_decode(
                np.asarray(d["prompt"], np.int32),
                int(np.asarray(d["n_new"])),
                temperature=float(np.asarray(d["temperature"])),
                top_k=int(np.asarray(d["top_k"])),
                seed=int(np.asarray(d["seed"])),
                deadline_ms=(None if dl is None
                             else float(np.asarray(dl)))),
                tid, parent)
        elif ftype == wire.RESUME:
            ckpt, tid, parent = \
                wire.decode_resume_payload(payload)
            admit_decode(rid,
                         lambda: engine.resume_decode(ckpt),
                         tid, parent)
        elif ftype == wire.WARM:
            arrays = wire.decode_tree(payload)
            try:
                warmed = engine.warmup(*arrays)
                send(wire.CTRL_OK, rid, json.dumps(
                    {"warmed": warmed}).encode("utf-8"))
            except BaseException as e:  # noqa: BLE001
                send(wire.ERR, rid, json.dumps(
                    wire.encode_error(e)).encode("utf-8"))
        elif ftype == wire.CTRL:
            op, arg = handle_ctrl(
                rid, json.loads(payload.decode("utf-8")))
            if op == "drain":
                return "drain" if arg else "fail"
        elif ftype == wire.FENCED:
            # mid-stream fence verdict: this connection (and in
            # connect mode this worker) is superseded
            try:
                reason = json.loads(
                    payload.decode("utf-8")).get("reason")
            except Exception:
                reason = "?"
            raise _Fenced(str(reason))
        return None

    if mode == "spawn":
        send(wire.HELLO, 0, json.dumps(
            {"token": token, "pid": os.getpid(),
             "name": name}).encode("utf-8"))
    # First heartbeat IMMEDIATELY: the router must never see a
    # just-started (or just-respawned) worker as stale for a whole
    # heartbeat interval — that window would eject every fresh boot.
    send_hb()
    threading.Thread(target=heartbeat_loop, daemon=True).start()
    threading.Thread(target=waiter_loop, daemon=True).start()

    # -- serve loop: one iteration per connection epoch -------------------
    drain_mode = None
    while drain_mode is None:
        sock.settimeout(0.2)
        lost = False
        try:
            for ftype, rid, payload in stash0:
                try:
                    drain_mode = dispatch(ftype, rid, payload) \
                        or drain_mode
                except OSError:
                    lost = True
                    break
                if drain_mode is not None:
                    break
            stash0 = []
            while drain_mode is None and not lost:
                try:
                    chunk = sock.recv(1 << 16)
                except socket.timeout:
                    continue
                except OSError:
                    if not tcp:
                        _log(f"{name}: socket error; exiting")
                        engine.stop(drain=False, drain_timeout_s=1.0)
                        return 1
                    lost = True
                    break
                if not chunk:
                    if not tcp:
                        _log(f"{name}: parent closed the pipe; "
                             "exiting")
                        engine.stop(drain=False, drain_timeout_s=1.0)
                        return 0
                    lost = True
                    break
                for ftype, rid, payload in reader.feed(chunk):
                    try:
                        drain_mode = dispatch(ftype, rid, payload) \
                            or drain_mode
                    except OSError:
                        lost = True
                        break
                    if drain_mode is not None:
                        break
        except wire.FrameCorruptError as e:
            if not tcp:
                _log(f"{name}: inbound frame corrupt ({e}); exiting "
                     "loudly")
                engine.stop(drain=False, drain_timeout_s=1.0)
                return 1
            # tcp: the CONNECTION is untrustworthy, the generation is
            # not — tear it down and re-handshake (fresh seqs both
            # directions)
            _log(f"{name}: inbound frame corrupt ({e}); "
                 "re-handshaking")
            lost = True
        except _Fenced as e:
            _log(f"{name}: fenced mid-stream ({e})")
            if mode == "connect":
                engine.stop(drain=False, drain_timeout_s=1.0)
                return 1
            state["fence"] = None  # listen: next adoption is fresh
            lost = True
        if drain_mode is not None or not lost:
            continue
        # -- connection lost (tcp): bounded re-adoption -------------------
        link_detach(sock)
        try:
            sock.close()
        except OSError:
            pass
        _log(f"{name}: connection lost; "
             + ("re-dialing parent" if mode == "connect"
                else "awaiting re-adoption"))
        got = redial() if mode == "connect" else accept_parent()
        if got is None:
            _log(f"{name}: no parent re-adopted us; exiting")
            engine.stop(drain=False, drain_timeout_s=1.0)
            return 1
        sock, welcome, reader, stash0 = got
        link_attach(sock, tx_seq=1)
        _log(f"{name}: "
             + (f"resumed generation (fence {state['fence']})"
                if welcome.get("resumed")
                else f"re-adopted fresh (fence {state['fence']})"))
        try:
            send_hb()  # immediately: never resume into staleness
        except OSError:
            pass

    # Drain: stop the engine (failing or serving the queue per mode),
    # flush EVERY outstanding future as a frame, then ship the final
    # counters — the reconciliation handshake — and exit 0.
    _log(f"{name}: draining ({drain_mode})")
    # Live KV-slab migration (ISSUE 17): checkpoint every in-flight
    # decode session BEFORE the engine stop can fail it — the
    # streamer threads turn each ServeMigratedError into a MIGRATE
    # frame, and the parent re-places the session on another replica
    # with zero token loss. Runs in BOTH drain modes: migrating a
    # session is strictly better than failing it.
    try:
        exported = engine.export_decode_sessions()
        if exported:
            _log(f"{name}: exported {len(exported)} live decode "
                 "session(s) for migration")
    except Exception as e:  # noqa: BLE001 — drain must proceed
        _log(f"{name}: decode-session export failed ({e!r})")
    engine.stop(drain=(drain_mode == "drain"))
    for t in decode_threads:
        # every session's terminal frame (REP/ERR/MIGRATE) must be on
        # the wire before the BYE handshake ships the final counters
        t.join(10.0)
    try:
        flush_done(block_all=True)
    except OSError:
        pass  # parent gone mid-drain: its death sweep owns the books
    stop_ev.set()
    if metrics is not None:
        metrics.close()
    try:
        bye = counters_payload()
        spans = trace_mod.drain_shipped(wire.SPANS_PER_BYE)
        if spans:
            # last chance for still-buffered spans to reach the
            # parent's merged timeline before a clean exit
            bye["spans"] = spans
        send(wire.BYE, 0, json.dumps(bye, default=str).encode("utf-8"))
        sock.close()
    except OSError:
        pass
    if lsock is not None:
        try:
            lsock.close()
        except OSError:
            pass
    _log(f"{name}: clean exit")
    return 0


if __name__ == "__main__":
    sys.exit(main())
