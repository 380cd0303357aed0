"""Step-timeline tracing, structured metrics logging, and the
device profiler's host plane (ISSUE 5) — the standard instrumentation
surface.

The reference's observability is a per-op wall-time table
(`Device::PrintTimeProfiling`); the TPU-native step is one opaque XLA
program, so op tables cannot say where a STEP spends its wall time —
waiting on the host input pipeline, dispatching the executable, or
blocked on the device. TVM (arXiv:1802.04799) makes the general point
(an optimizing stack is only as good as its cost visibility) and
µ-cuDNN (arXiv:1804.04806) the specific one (per-microbatch timing is
what justifies decomposition choices). Three pieces:

  - **Span tracer** — `span(name)` context managers, nestable and
    thread-safe, recorded into a bounded ring buffer. Disabled (the
    default) it is a strict no-op: `span()` returns a shared null
    context, nothing is recorded, nothing allocates. Spans are
    pre-wired through the whole step path (`data.BatchIter`
    data-wait, eager `train_one_batch` + the fused optimizer apply,
    the compiled step's phases (below), `run_resumable`'s loss read
    (`device_sync`: the one place a loop waits for the device) and
    checkpoint save/restore). Enable: `device.set_tracing(True)`.
    Export: `export_chrome_trace(path)` (Chrome trace-event /
    Perfetto JSON) or the per-step `format_summary()` table.
  - **Device-trace sink** — an enabled `span()` is also a
    `jax.profiler.TraceAnnotation("singa:" + name)`: whenever any
    profiler session runs (`jax.profiler.start_trace`), every span
    lands in the trace's `/host:CPU` plane on the device trace's
    clock, on the thread that did the work, under the device's
    operations. No session running, it costs under a microsecond.
  - **Phases** — `phase(name)`: what a job does once a step, the
    compiled step's `step.call` / `step.place` / `step.enqueue` /
    `step.bind` (`model._JitStep.__call__`; `step` alone stays the
    loop's own `step_span`, one a step). A phase is such an
    annotation ALWAYS, so a profiler session over a live job shows
    them with no switch thrown, and a span in the ring while the
    tracer is on. None of them waits for the device.
  - **MetricsLogger** — one schema-stable JSONL record per training
    step (step, loss, examples/sec, data-wait / dispatch /
    device-sync seconds, `cache_stats` counter deltas,
    resilience/accum counters, registered eval metrics), flushed
    record-atomically so a killed run (PR 3's `fit_resumable`)
    leaves a parseable log — `read_metrics` tolerates the one
    partial trailing line a kill mid-write can leave.

Counters surface in `cache_stats()["trace"]` and reset with
`reset_cache_stats()` (ring entries survive the reset — resetting
observability must not lose the timeline, the same contract as the
executable caches keeping their entries).
"""
from __future__ import annotations

import bisect
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from . import stats as stats_mod

__all__ = [
    "configure",
    "get_config",
    "enabled",
    "span",
    "phase",
    "record_span",
    "step_span",
    "records",
    "clear",
    "last_step_timings",
    "export_chrome_trace",
    "merge_chrome_traces",
    "aggregate_fleet",
    "span_summary",
    "format_summary",
    "new_trace_id",
    "context",
    "current_trace",
    "current_span_id",
    "drain_shipped",
    "OffsetEstimator",
    "MetricsLogger",
    "read_metrics",
]

# v2 (ISSUE 15): records additionally carry the writer `pid` and a
# `mono` perf_counter stamp paired with the wall-clock `time`, so
# multi-process logs are time-alignable offline. Additive only —
# `read_metrics` parses v1 and v2 records alike.
SCHEMA_VERSION = 2

_LOCK = threading.RLock()
_ENABLED = False
_RING: deque = deque(maxlen=16384)
_NEXT_ID = itertools.count(1)  # .__next__ is atomic in CPython
_TLS = threading.local()
# jax.profiler.TraceAnnotation, imported by the first enabled span
_ANNOTATION = None
_LAST_STEP: Optional[Dict] = None
# Cross-process span ship-back (ISSUE 15): spans carrying a trace
# context are ALSO buffered here when a capacity is armed
# (`configure(ship_capacity=n)`), for a transport to drain and ship to
# the parent process in bounded chunks. 0 = off (the default — only
# fleet workers arm it).
_SHIP: deque = deque()
_SHIP_CAP = 0


class _TraceStats:
    """cache_stats()["trace"]: spans recorded / dropped by the ring /
    step spans closed / chrome exports written / ship-back buffer
    accounting (buffered spans drained for cross-process shipping,
    drops when the bounded buffer overflows). reset() zeroes the
    counters; the ring itself is cleared only by `trace.clear()`."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.spans = 0
        self.dropped = 0
        self.steps = 0
        self.exports = 0
        self.shipped = 0
        self.ship_dropped = 0

    def snapshot(self) -> Dict:
        return {
            "enabled": _ENABLED,
            "spans": self.spans,
            "dropped": self.dropped,
            "steps": self.steps,
            "exports": self.exports,
            "shipped": self.shipped,
            "ship_dropped": self.ship_dropped,
            "ship_pending": len(_SHIP),
            "ring_size": len(_RING),
            "ring_capacity": _RING.maxlen,
        }


_STATS = _TraceStats()
stats_mod.register_cache("trace", _STATS)


# ---------------------------------------------------------------------------
# Config (user-facing setter: device.set_tracing — the reference's
# config surface, same pattern as every other knob).
# ---------------------------------------------------------------------------
def configure(enabled: Optional[bool] = None,
              ring_capacity: Optional[int] = None,
              ship_capacity: Optional[int] = None) -> Dict:
    global _ENABLED, _RING, _SHIP_CAP
    with _LOCK:
        if ring_capacity is not None:
            cap = int(ring_capacity)
            if cap < 1:
                raise ValueError("ring_capacity must be >= 1")
            if cap != _RING.maxlen:
                _RING = deque(_RING, maxlen=cap)
        if ship_capacity is not None:
            cap = int(ship_capacity)
            if cap < 0:
                raise ValueError("ship_capacity must be >= 0 (0=off)")
            _SHIP_CAP = cap
            if cap == 0:
                _SHIP.clear()
        if enabled is not None:
            _ENABLED = bool(enabled)
    return get_config()


def get_config() -> Dict:
    return {"enabled": _ENABLED, "ring_capacity": _RING.maxlen,
            "ship_capacity": _SHIP_CAP}


def enabled() -> bool:
    return _ENABLED


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------
def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _ctx_stack() -> list:
    st = getattr(_TLS, "trace_stack", None)
    if st is None:
        st = _TLS.trace_stack = []
    return st


# ---------------------------------------------------------------------------
# Trace context (ISSUE 15): one request = one trace_id, born at the
# fleet router's submit and threaded through failover hops, client
# retries, and the process boundary, so every span a request touches —
# in any thread, in any PROCESS — carries the same id and the merged
# timeline can answer "where did this p99 request spend its time".
# ---------------------------------------------------------------------------
def new_trace_id() -> str:
    """A fresh 16-hex-char trace id, unique across processes."""
    import binascii

    return binascii.hexlify(os.urandom(8)).decode("ascii")


class _TraceCtx:
    """Thread-local trace-context frame: spans opened (or recorded via
    `record_span`) while it is active carry `trace` = the trace id;
    top-level spans additionally carry `remote_parent` — the span id
    in the ORIGINATING process under which they causally nest."""

    __slots__ = ("trace_id", "parent")

    def __init__(self, trace_id: str, parent):
        self.trace_id = trace_id
        self.parent = parent

    def __enter__(self):
        _ctx_stack().append(self)
        return self

    def __exit__(self, *exc):
        st = _ctx_stack()
        if st and st[-1] is self:
            st.pop()
        else:  # mismatched teardown: best-effort
            try:
                st.remove(self)
            except ValueError:
                pass
        return False


def context(trace_id: Optional[str] = None, parent=None):
    """Activate a trace context for the calling thread. With tracing
    disabled (or no id) this is the shared null context — strict
    no-op, nothing allocates, nothing propagates."""
    if not _ENABLED or trace_id is None:
        return _NULL
    return _TraceCtx(str(trace_id),
                     None if parent is None else int(parent))


def current_trace() -> Optional[Dict]:
    """The active trace context: {"trace_id", "parent"} or None."""
    st = getattr(_TLS, "trace_stack", None)
    if not st:
        return None
    c = st[-1]
    return {"trace_id": c.trace_id, "parent": c.parent}


def current_span_id() -> Optional[int]:
    """Id of the innermost OPEN span on this thread (the natural
    parent for work handed to another thread/process), or None."""
    st = getattr(_TLS, "stack", None)
    return st[-1].id if st else None


def _normalize_trace(trace):
    """(trace_id, parent) from a str / (id, parent) tuple / context
    dict / None."""
    if trace is None:
        return None, None
    if isinstance(trace, str):
        return trace, None
    if isinstance(trace, dict):
        return trace.get("trace_id"), trace.get("parent")
    tid = trace[0]
    parent = trace[1] if len(trace) > 1 else None
    return (None if tid is None else str(tid)), parent


def _ship(rec: Dict) -> None:
    """Buffer a trace-stamped span for cross-process ship-back.
    Bounded: overflow drops the OLDEST span and counts it — frames
    stay bounded, memory stays bounded, the loss is loud in
    `cache_stats()["trace"]["ship_dropped"]`. Only the fields the
    merged timeline needs are copied (wire bytes are request-path
    cost). Caller holds _LOCK."""
    if _SHIP_CAP <= 0:
        return
    if len(_SHIP) >= _SHIP_CAP:
        _SHIP.popleft()
        _STATS.ship_dropped += 1
    slim = {"name": rec["name"], "ts": rec["ts"], "dur": rec["dur"],
            "tid": rec["tid"], "trace": rec["trace"]}
    if rec.get("remote_parent") is not None:
        slim["remote_parent"] = rec["remote_parent"]
    if rec.get("args"):
        slim["args"] = rec["args"]
    _SHIP.append(slim)


def ship_backlog() -> tuple:
    """(buffered, capacity) of the ship-back buffer — transports use
    the pressure signal to decide whether to piggyback spans on a
    REPLY frame (request-path bytes, spent only when heartbeats are
    not keeping up) or leave them for the next heartbeat."""
    return len(_SHIP), _SHIP_CAP


def drain_shipped(max_n: int) -> List[Dict]:
    """Pop up to `max_n` buffered spans for shipping (oldest first).
    The per-call bound is the per-FRAME bound: a reply or heartbeat
    frame carries at most this many piggybacked spans, never an
    unbounded backlog."""
    out: List[Dict] = []
    with _LOCK:
        while _SHIP and len(out) < int(max_n):
            out.append(_SHIP.popleft())
        _STATS.shipped += len(out)
    return out


class _NullSpan:
    """The disabled-tracer span: a shared, stateless no-op context."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    # dur_s: the span's own measured duration, readable after exit —
    # a caller double-timing the same work (the online-SLO sketch
    # cross-validated against this very span) must feed the IDENTICAL
    # value, not a second clock read that diverges under load.
    __slots__ = ("name", "args", "id", "parent", "depth", "t0",
                 "dur_s", "_ann")

    def __init__(self, name: str, args: Optional[Dict]):
        self.name = name
        self.args = args

    def __enter__(self):
        # the span's args stay out of the name: readers match on it
        self._ann = _annotation()("singa:" + self.name)
        self._ann.__enter__()
        st = _stack()
        self.depth = len(st)
        self.parent = st[-1].id if st else None
        self.id = next(_NEXT_ID)
        st.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        try:
            self._record(t1)
        finally:
            # the annotation opens first and closes last: in a device
            # trace the span's own bookkeeping is inside the span
            self._ann.__exit__(*exc)
        return False

    def _record(self, t1: float) -> None:
        self.dur_s = t1 - self.t0
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        else:  # mismatched exit (generator teardown): best-effort
            try:
                st.remove(self)
            except ValueError:
                pass
        frame = getattr(_TLS, "step_frame", None)
        rec = {
            "name": self.name,
            # µs on the shared perf_counter clock (what Chrome "ts"
            # wants; absolute origin is irrelevant, only deltas are)
            "ts": self.t0 * 1e6,
            "dur": (t1 - self.t0) * 1e6,
            "tid": threading.get_ident(),
            "id": self.id,
            "parent": self.parent,
            "depth": self.depth,
            "step": frame["step"] if frame is not None else None,
        }
        if self.args:
            rec["args"] = self.args
        ctx = getattr(_TLS, "trace_stack", None)
        if ctx:
            c = ctx[-1]
            rec["trace"] = c.trace_id
            if self.parent is None and c.parent is not None:
                rec["remote_parent"] = c.parent
        with _LOCK:
            if not _ENABLED:
                return  # disabled mid-span: drop silently
            if len(_RING) == _RING.maxlen:
                _STATS.dropped += 1
            _RING.append(rec)
            _STATS.spans += 1
            if "trace" in rec:
                _ship(rec)
            if frame is not None and self.name != "step":
                acc = frame["acc"]
                acc[self.name] = acc.get(self.name, 0.0) + (t1 - self.t0)


def span(name: str, **args):
    """Context manager timing one named host span. Nests (thread-local
    stack fixes depth/parent), records into the bounded ring on exit,
    and is a `jax.profiler.TraceAnnotation("singa:" + name)` meanwhile,
    so a running profiler session shows it on the device trace's clock.
    Strict no-op while tracing is disabled: the shared `_NULL` context
    is returned, nothing is recorded or allocated."""
    if not _ENABLED:
        return _NULL
    return _Span(name, args or None)


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


def phase(name: str):
    """Context manager for a once-a-step host phase. Tracer on or
    off it is a `jax.profiler.TraceAnnotation("singa:" + name)`: any
    profiler session, a benchmark's traced window or an operator's
    capture of a live job, shows it on the host thread under the
    device's operations. While the tracer is on it is a `span` (ring,
    step frame) as well. For the few phases of a step, not for hot
    paths: unlike a disabled `span()` it allocates."""
    if _ENABLED:
        return _Span(name, None)
    return _annotation()("singa:" + name)


def record_span(name: str, t0: float, t1: float, trace=None,
                **args) -> None:
    """Record an already-measured span from explicit `perf_counter`
    endpoints. The context-manager `span()` times work on ONE thread;
    a latency that starts on one thread and ends on another — a
    serving request's `queue_wait`, measured from the submitter's
    enqueue to the dispatcher's dequeue — can only be recorded after
    the fact. Same ring, same drop accounting, same strict no-op while
    tracing is disabled. Ring only: an annotation cannot be opened in
    the past, so a profiler session's trace does not show these spans.
    Top-level by construction (no parent): the two endpoint threads
    have different span stacks, so nesting is undefined. `trace`
    attaches a trace context explicitly — a str trace id or a
    (trace_id, parent_span_id) pair — for spans whose owning request
    lives on another thread; None falls back to the calling thread's
    active context."""
    if not _ENABLED:
        return
    rec = {
        "name": name,
        "ts": t0 * 1e6,
        "dur": max(t1 - t0, 0.0) * 1e6,
        "tid": threading.get_ident(),
        "id": next(_NEXT_ID),
        "parent": None,
        "depth": 0,
        "step": None,
    }
    tid, parent = _normalize_trace(trace)
    if tid is None:
        ctx = current_trace()
        if ctx is not None:
            tid, parent = ctx["trace_id"], ctx["parent"]
    if tid is not None:
        rec["trace"] = tid
        if parent is not None:
            rec["remote_parent"] = parent
    if args:
        rec["args"] = args
    with _LOCK:
        if not _ENABLED:
            return
        if len(_RING) == _RING.maxlen:
            _STATS.dropped += 1
        _RING.append(rec)
        _STATS.spans += 1
        if "trace" in rec:
            _ship(rec)


class _StepCtx:
    """One training step: opens a "step" span, accumulates child span
    durations by name (the per-step data_wait / dispatch / device_sync
    decomposition `MetricsLogger` reads via `last_step_timings`)."""

    __slots__ = ("step", "_span", "_frame", "_prev_frame", "_t0")

    def __init__(self, step):
        self.step = step

    def __enter__(self):
        self._prev_frame = getattr(_TLS, "step_frame", None)
        self._frame = {"step": self.step, "acc": {}}
        _TLS.step_frame = self._frame
        self._t0 = time.perf_counter()
        self._span = _Span("step", None)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        global _LAST_STEP
        self._span.__exit__(*exc)
        wall = time.perf_counter() - self._t0
        _TLS.step_frame = self._prev_frame
        acc = self._frame["acc"]
        summary = {
            "step": self.step,
            "step_s": wall,
            "data_wait_s": acc.get("data_wait", 0.0),
            # the compiled step's enqueue phase; the loop's own wait
            # where it reads the loss (`resilience.run_resumable`)
            "dispatch_s": acc.get("step.enqueue", 0.0),
            "device_sync_s": acc.get("device_sync", 0.0),
        }
        with _LOCK:
            if _ENABLED:
                _LAST_STEP = summary
                _STATS.steps += 1
        return False


def step_span(step=None):
    """Context manager for ONE training step of a loop. While tracing
    is enabled it opens a "step" span whose children (data_wait /
    step.enqueue / device_sync, emitted by the wired step path) become
    the per-step decomposition; the compiled step's own `step.call`
    phase nests inside it. A strict no-op when tracing is off."""
    if not _ENABLED:
        return _NULL
    return _StepCtx(step)


def records() -> List[Dict]:
    """Snapshot of the span ring (oldest first)."""
    with _LOCK:
        return [dict(r) for r in _RING]


def clear() -> None:
    """Drop all recorded spans, the ship-back buffer, and the
    last-step summary (counters survive; use `reset_cache_stats()`
    for those)."""
    global _LAST_STEP
    with _LOCK:
        _RING.clear()
        _SHIP.clear()
        _LAST_STEP = None


def last_step_timings() -> Optional[Dict]:
    """The most recent closed step span's timing decomposition:
    {step, step_s, data_wait_s, dispatch_s, device_sync_s}. None until
    a step span closes with tracing enabled."""
    with _LOCK:
        return dict(_LAST_STEP) if _LAST_STEP else None


class OffsetEstimator:
    """Remote-monotonic-clock offset from request/reply round trips
    (ISSUE 18): `remote perf_counter + offset_us()/1e6 == local
    perf_counter`, within `uncertainty_us()`.

    Each `add(t_send, t_recv, t_remote)` is one round trip: the local
    send/receive stamps bracket the remote stamp, so the midpoint
    minus the remote stamp estimates the offset with error bounded by
    RTT/2 (classic NTP discipline). Over a real network the error is
    dominated by QUEUEING, not the path: a frame delayed in ONE
    direction biases its midpoint by delay/2 but also inflates its
    RTT — so the estimator keeps only the `k` smallest-RTT samples
    and reports the MEDIAN of their offsets. Clean round trips sink
    to the front and injected asymmetric delay is filtered out rather
    than averaged in; the median guards the case where every sample
    is jittered. `uncertainty_us()` is the best RTT's half-width —
    the bound the transport's offset-sanity pin checks against."""

    __slots__ = ("k", "_best")

    def __init__(self, k: int = 5):
        if k < 1:
            raise ValueError("k must be >= 1")
        self.k = int(k)
        self._best: List[tuple] = []  # (rtt_s, offset_us), rtt-sorted

    def add(self, t_send: float, t_recv: float,
            t_remote: float) -> None:
        rtt = float(t_recv) - float(t_send)
        if rtt < 0.0:
            return  # caller bug or clock step; never poison the pool
        off = ((float(t_send) + float(t_recv)) / 2.0
               - float(t_remote)) * 1e6
        bisect.insort(self._best, (rtt, off))
        del self._best[self.k:]

    @property
    def n(self) -> int:
        return len(self._best)

    def rtt_s(self) -> Optional[float]:
        """Smallest RTT seen (seconds); None before any sample."""
        return self._best[0][0] if self._best else None

    def offset_us(self) -> Optional[float]:
        """Median offset over the k smallest-RTT samples (µs)."""
        if not self._best:
            return None
        offs = sorted(o for _, o in self._best)
        m = len(offs) // 2
        if len(offs) % 2:
            return offs[m]
        return (offs[m - 1] + offs[m]) / 2.0

    def uncertainty_us(self) -> Optional[float]:
        """Half the best RTT (µs) — the midpoint estimate's error
        bound; None before any sample."""
        if not self._best:
            return None
        return self._best[0][0] * 1e6 / 2.0


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------
def export_chrome_trace(path: str) -> str:
    """Write the span ring as Chrome trace-event JSON (the
    `chrome://tracing` / Perfetto `traceEvents` format: complete "X"
    events with µs ts/dur, nested by time containment per pid/tid).
    Atomic: written to a temp file and renamed into place."""
    pid = os.getpid()
    with _LOCK:
        recs = list(_RING)
    events = [_chrome_event(r, pid, 0.0) for r in recs]
    return _write_chrome(path, events)


def _chrome_event(r: Dict, default_pid: int, offset_us: float) -> Dict:
    """One ring record (or an already-chrome event) as a Chrome
    trace-event, with `offset_us` added to its timestamp — the clock
    alignment hook `merge_chrome_traces` applies per source."""
    ev = {"name": r["name"], "ph": r.get("ph", "X"),
          "cat": r.get("cat", "singa_tpu"),
          "ts": round(float(r["ts"]) + offset_us, 3),
          "dur": round(float(r.get("dur", 0.0)), 3),
          "pid": r.get("pid", default_pid), "tid": r.get("tid", 0)}
    args = dict(r.get("args") or {})
    for k in ("step", "trace", "remote_parent"):
        if r.get(k) is not None:
            args[k] = r[k]
    if args:
        ev["args"] = args
    return ev


def _write_chrome(path: str, events: List[Dict]) -> str:
    events.sort(key=lambda e: (e["ts"], -e.get("dur", 0.0)))
    payload = {"traceEvents": events, "displayTimeUnit": "ms"}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    with _LOCK:
        _STATS.exports += 1
    return path


def merge_chrome_traces(path: str, sources) -> str:
    """Merge span records from MANY processes into ONE Chrome/Perfetto
    timeline (ISSUE 15). Each source is a dict:

      records    span records (ring records, shipped worker spans, or
                 already-chrome events) — or
      path       a Chrome trace JSON file to fold in;
      pid        the pid to stamp on this source's events (default:
                 the records' own, else this process);
      offset_us  added to every timestamp — the per-worker
                 monotonic-clock offset the proc transport estimates
                 from the REQ→ACK handshake, so spans measured on N
                 different `perf_counter` origins land on ONE aligned
                 axis and a request's router/IPC/worker spans nest by
                 time containment across pids.

    Atomic write; returns `path`."""
    default_pid = os.getpid()
    events: List[Dict] = []
    for src in sources:
        recs = src.get("records")
        if recs is None and src.get("path"):
            try:
                with open(src["path"], "r", encoding="utf-8") as f:
                    data = json.load(f)
            except (OSError, ValueError):
                continue
            recs = (data.get("traceEvents", [])
                    if isinstance(data, dict) else data)
        pid = src.get("pid")
        off = float(src.get("offset_us") or 0.0)
        for r in recs or []:
            ev = _chrome_event(r, default_pid, off)
            if pid is not None:
                ev["pid"] = pid
            events.append(ev)
    return _write_chrome(path, events)


def span_summary() -> Dict[str, Dict]:
    """Aggregate the ring by span name:
    name -> {count, total_ms, mean_ms, max_ms}."""
    out: Dict[str, Dict] = {}
    for r in records():
        s = out.setdefault(r["name"],
                           {"count": 0, "total_ms": 0.0, "max_ms": 0.0})
        d = r["dur"] / 1e3
        s["count"] += 1
        s["total_ms"] += d
        if d > s["max_ms"]:
            s["max_ms"] = d
    for s in out.values():
        s["mean_ms"] = round(s["total_ms"] / s["count"], 4)
        s["total_ms"] = round(s["total_ms"], 4)
        s["max_ms"] = round(s["max_ms"], 4)
    return out


def format_summary() -> str:
    """The per-step summary table: one row per span name with count,
    total/mean/max ms, and ms per step (total over the step spans in
    the ring) — the at-a-glance answer to "where does a step go"."""
    snap = span_summary()
    n_steps = max(snap.get("step", {}).get("count", 0), 1)
    lines = [f"trace summary ({n_steps} step span(s) in ring):",
             f"  {'span':<22} {'count':>7} {'total_ms':>10} "
             f"{'mean_ms':>9} {'max_ms':>9} {'ms/step':>9}"]
    for name, s in sorted(snap.items(), key=lambda kv: -kv[1]["total_ms"]):
        lines.append(
            f"  {name:<22} {s['count']:>7d} {s['total_ms']:>10.3f} "
            f"{s['mean_ms']:>9.3f} {s['max_ms']:>9.3f} "
            f"{s['total_ms'] / n_steps:>9.3f}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Structured metrics log (JSONL, one record per train step).
# ---------------------------------------------------------------------------
def _json_default(v):
    if isinstance(v, (np.floating, np.integer)):
        return v.item()
    if isinstance(v, np.ndarray):
        return v.tolist()
    return str(v)


class MetricsLogger:
    """Append-only JSONL training log: ONE schema-stable record per
    training step, written as a single flush-per-record append so a
    SIGKILL mid-run leaves every completed record parseable
    (`read_metrics` skips the at-most-one partial trailing line).

    Record fields (always present, None when unknown): schema, time,
    pid, mono (wall/monotonic clock pair + writer pid — v2, ISSUE 15:
    multi-process fleet logs align offline), step, loss,
    examples_per_sec, step_s, data_wait_s, dispatch_s,
    device_sync_s (from the tracer's last closed step span when
    tracing is on), cache (per-cache COUNTER DELTAS since the previous
    record — retraces/step after warmup ≈ 0 is the healthy signal;
    live-state gauges, high-water marks, ratios and config knobs —
    the `_GAUGE_KEYS` set: slots_in_use, queue_depth, ring_size,
    size, occupancy, … — are passed through ABSOLUTE, since the
    delta of a gauge is signed noise: occupancy dropping between
    records would render as a negative "counter"),
    resilience + accum (absolute counters from `cache_stats()`),
    metrics (registered eval metrics — `Metric.register(logger)`),
    extra (caller keyword passthrough).

    `fsync=True` additionally fsyncs every record (survives OS crash,
    not just process kill) — off by default, it serializes the step
    loop on disk latency."""

    def __init__(self, path: str, fsync: bool = False):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "ab")
        self._fsync = bool(fsync)
        self._lock = threading.Lock()
        self._prev_cache: Optional[Dict] = None
        self._metrics: Dict[str, object] = {}
        self.records_written = 0

    # -- metric registration (singa_tpu.metric.Metric.register) ----------
    def register_metric(self, name: str, metric) -> None:
        """Evaluate `metric` (anything with `.evaluate(outputs,
        labels) -> float`) into every record whose `log_step` call
        passes outputs/labels; the value lands under
        `record["metrics"][name]` — eval metrics in the same stream as
        the loss."""
        self._metrics[str(name)] = metric

    # Cache-snapshot fields that are NOT monotone counters: live-state
    # gauges (a shrinking gauge would delta negative), high-water
    # marks (reset() restarts them), derived ratios and config knobs
    # (whose deltas are meaningless). These pass through the delta
    # transform absolute.
    _GAUGE_KEYS = frozenset({
        # decode slot pool / LRU cache occupancy
        "slots", "slots_in_use", "size", "negative_size", "capacity",
        "host_leaves_per_call",
        # serve queue live state, watermarks, derived ratios
        "queue_depth", "max_queue_depth", "effective_wait_ms",
        "coalesce_mean", "occupancy", "max_coalesce",
        # trace ring occupancy / config
        "ring_size", "ring_capacity", "ship_pending",
        # dag_route config knob
        "flops_per_op_threshold",
    })
    # and the live slab's bytes by kind, whatever kinds a model states
    _GAUGE_PREFIX = "cache_bytes_"

    # -- record construction ----------------------------------------------
    def _cache_delta(self, snap: Dict) -> Dict:
        """Per-cache numeric-counter deltas vs the previous record
        (resilience/accum are reported absolute elsewhere; the
        `_GAUGE_KEYS` gauge/watermark/ratio fields are absolute
        too)."""
        cur: Dict = {}
        for name, s in snap.items():
            if name in ("resilience", "accum"):
                continue
            if isinstance(s, dict):
                cur[name] = {
                    k: v for k, v in s.items()
                    if isinstance(v, (int, float))
                    and not isinstance(v, bool)}
            elif isinstance(s, (int, float)) and not isinstance(s, bool):
                cur[name] = s
        prev = self._prev_cache or {}
        out: Dict = {}
        for name, s in cur.items():
            if isinstance(s, dict):
                p = prev.get(name, {})
                if not isinstance(p, dict):
                    p = {}
                out[name] = {
                    k: (v if k in self._GAUGE_KEYS
                        or k.startswith(self._GAUGE_PREFIX)
                        else round(v - p.get(k, 0), 6)
                        if isinstance(v, float) else v - p.get(k, 0))
                    for k, v in s.items()}
            else:
                p = prev.get(name, 0)
                out[name] = s - (p if isinstance(p, (int, float)) else 0)
        self._prev_cache = cur
        return out

    def log_step(self, step, loss=None, examples=None, step_s=None,
                 outputs=None, labels=None, **extra) -> Dict:
        """Append the record for `step`. `loss` may be a Tensor /
        device scalar / float; `examples` is the batch's sample count
        (drives examples_per_sec); `step_s` overrides the tracer's
        step wall time (pass it when no step span wrapped the step).
        `outputs`/`labels` feed the registered eval metrics. Returns
        the record dict."""
        t = last_step_timings()
        if t is not None and t.get("step") not in (None, step):
            t = None  # stale frame from a different step: don't misattribute
        if step_s is None and t is not None:
            step_s = t["step_s"]
        snap = stats_mod.cache_stats()
        if loss is not None:
            loss = float(np.asarray(
                loss.to_numpy() if hasattr(loss, "to_numpy") else loss))
        if outputs is not None and labels is not None:
            mvals = {name: float(m.evaluate(outputs, labels))
                     for name, m in self._metrics.items()}
        else:
            mvals = {name: None for name in self._metrics}
        rec = {
            "schema": SCHEMA_VERSION,
            "time": round(time.time(), 3),
            # Writer pid + a monotonic stamp PAIRED with the wall
            # clock above (ISSUE 15): N per-process logs are
            # time-alignable offline — the (time, mono) pair in any
            # record recovers each process's perf_counter->wall
            # offset. Additive: read_metrics parses v1 records (no
            # pid/mono) and v2 alike.
            "pid": os.getpid(),
            "mono": round(time.perf_counter(), 6),
            "step": int(step),
            "loss": loss,
            "step_s": None if step_s is None else round(float(step_s), 6),
            "data_wait_s": round(t["data_wait_s"], 6) if t else None,
            "dispatch_s": round(t["dispatch_s"], 6) if t else None,
            "device_sync_s": round(t["device_sync_s"], 6) if t else None,
            "examples_per_sec": (
                round(float(examples) / float(step_s), 2)
                if examples and step_s else None),
            "cache": self._cache_delta(snap),
            "resilience": dict(snap.get("resilience", {})),
            "accum": dict(snap.get("accum", {})),
            "metrics": mvals,
            "extra": dict(extra),
        }
        self._write(rec)
        return rec

    def _write(self, rec: Dict) -> None:
        # one encode + one write + one flush per record: a kill lands
        # between records (or mid-way through at most the last line)
        data = (json.dumps(rec, sort_keys=True, default=_json_default)
                + "\n").encode("utf-8")
        with self._lock:
            self._f.write(data)
            self._f.flush()
            if self._fsync:
                os.fsync(self._f.fileno())
            self.records_written += 1

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def read_metrics(path: str) -> List[Dict]:
    """Parse a metrics JSONL. Tolerant of the one artifact a killed
    run can leave — a partial trailing line — and of any interleaved
    garbage: non-JSON lines are skipped, never raised on."""
    out: List[Dict] = []
    try:
        f = open(path, "r", encoding="utf-8", errors="replace")
    except OSError:
        return out
    with f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


# ---------------------------------------------------------------------------
# Fleet telemetry aggregator (ISSUE 15): N per-replica/worker metrics
# JSONL streams + the merged span timeline -> ONE schema-stable fleet
# record. Rendered by `tools/fleet_top.py`.
# ---------------------------------------------------------------------------
FLEET_AGGREGATE_SCHEMA = 1

# The per-segment latency decomposition: where a fleet request's time
# goes, one bucket per span name on the request path.
FLEET_SEGMENTS = ("queue_wait", "ipc", "dispatch", "reply", "route",
                  "failover", "submit", "batch_assemble",
                  # decode-tier SLO edges (ISSUE 16): time-to-first-
                  # token and time-per-output-token — additive;
                  # _segment_stats only emits names actually present
                  "ttft", "tpot")


def _segment_stats(spans) -> Dict[str, Dict]:
    by_name: Dict[str, List[float]] = {}
    for r in spans or []:
        name = r.get("name")
        if name in FLEET_SEGMENTS and r.get("dur") is not None:
            by_name.setdefault(name, []).append(float(r["dur"]) / 1e3)
    out: Dict[str, Dict] = {}
    for name, ms in by_name.items():
        arr = np.asarray(ms)
        out[name] = {
            "count": len(ms),
            "p50_ms": round(float(np.percentile(arr, 50)), 3),
            "p99_ms": round(float(np.percentile(arr, 99)), 3),
        }
    return out


def _load_chrome_events(chrome_trace: Optional[str]) -> List:
    """Events from a `merge_chrome_traces` output file (tolerant:
    unreadable/garbled files contribute nothing, matching
    `aggregate_fleet`'s behaviour)."""
    if not chrome_trace:
        return []
    try:
        with open(chrome_trace, "r", encoding="utf-8") as f:
            data = json.load(f)
        return list(data.get("traceEvents", [])
                    if isinstance(data, dict) else data)
    except (OSError, ValueError):
        return []


def fleet_segment_samples_ms(spans=None,
                             chrome_trace: Optional[str] = None
                             ) -> Dict[str, List[float]]:
    """Raw per-segment latency samples in ms, SORTED ascending — the
    post-hoc side of the ISSUE 20 online-SLO cross-validation.  Same
    span selection as `_segment_stats` (names in `FLEET_SEGMENTS`,
    `dur` present), but returning the samples themselves so a caller
    can apply the *sketch's* rank convention — ``rank = q*(n-1)``,
    value = first sample whose cumulative count exceeds ``rank``,
    i.e. ``sorted[floor(rank)]`` — instead of `np.percentile`'s
    interpolation, which disagrees at small n by more than the
    sketch's relative-error bound and would fail the gate spuriously."""
    all_spans = list(spans or [])
    all_spans.extend(_load_chrome_events(chrome_trace))
    out: Dict[str, List[float]] = {}
    for r in all_spans:
        name = r.get("name")
        if name in FLEET_SEGMENTS and r.get("dur") is not None:
            out.setdefault(name, []).append(float(r["dur"]) / 1e3)
    for v in out.values():
        v.sort()
    return out


def aggregate_fleet(paths=None, spans=None,
                    chrome_trace: Optional[str] = None) -> Dict:
    """Roll fleet telemetry into ONE schema-stable record:

      paths         metrics JSONL files (or directories globbed for
                    `*.jsonl`): the router's control-plane stream
                    (records whose `extra.event` is set) and the
                    per-replica/worker serving streams (per-dispatch
                    records) — both the `read_metrics` format, v1 or
                    v2 records alike.
      spans         span records (ring records or chrome events) for
                    the per-segment latency decomposition.
      chrome_trace  a merged Chrome trace file whose events join
                    `spans` (the `merge_chrome_traces` output).

    Returns {schema, kind, requests/replies/failed/rejected + routing
    counters, availability_pct, segments (queue/ipc/dispatch/reply/
    ttft/tpot/... p50/p99), events (the ejection/restart/kill
    state-transition timeline), workers (per-pid dispatch totals),
    decode (session terminals + migration/replay counts, ISSUE 17),
    replica_decode (per-replica session occupancy from the router's
    final record), trace_ids}. Every field is always present
    (None/empty when the inputs don't carry it) — the schema-stable
    contract every consumer pins on."""
    import glob as glob_mod

    files: List[str] = []
    for p in (paths or []):
        if os.path.isdir(p):
            files.extend(sorted(glob_mod.glob(os.path.join(p,
                                                           "*.jsonl"))))
        else:
            files.append(p)
    counters: Dict[str, int] = {}
    events: List[Dict] = []
    workers: Dict[str, Dict] = {}
    replica_decode: Dict[str, Dict] = {}
    for f in files:
        for rec in read_metrics(f):
            x = rec.get("extra") or {}
            if x.get("event"):
                # router control-plane record: counters are monotone
                # within a run — keep the max seen
                for k in ("fleet_requests", "fleet_replies",
                          "fleet_failed", "routed", "failovers",
                          "refused", "rejected", "ejections",
                          "rejoins", "restarts", "kills_injected",
                          "decode_requests", "decode_replies",
                          "decode_failed", "decode_migrations",
                          "decode_replays"):
                    v = x.get(k)
                    if isinstance(v, (int, float)):
                        counters[k] = max(counters.get(k, 0), int(v))
                # per-replica decode occupancy (ISSUE 17): the router
                # attaches a snapshot to its final "stop" record —
                # last writer wins (the freshest view of each replica)
                rd = x.get("replica_decode")
                if isinstance(rd, dict):
                    replica_decode.update(rd)
                if x["event"] == "transition":
                    events.append({
                        "t": rec.get("time"),
                        "replica": x.get("replica"),
                        "to_state": x.get("to_state"),
                        "reason": x.get("reason"),
                    })
            elif x.get("bucket") is not None:
                # per-dispatch serving record (engine or worker side)
                key = str(rec.get("pid") or os.path.basename(f))
                w = workers.setdefault(key, {
                    "dispatches": 0, "rows": 0, "expired": 0,
                    "shed": 0, "retries": 0, "failed": 0})
                w["dispatches"] += 1
                w["rows"] += int(x.get("rows") or 0)
                for k in ("expired", "shed", "retries", "failed"):
                    v = x.get(k)
                    if isinstance(v, (int, float)):
                        w[k] = max(w[k], int(v))  # cumulative in-stream
    all_spans = list(spans or [])
    all_spans.extend(_load_chrome_events(chrome_trace))
    trace_ids = set()
    for r in all_spans:
        t = r.get("trace") or (r.get("args") or {}).get("trace")
        if t:
            trace_ids.add(t)
    req = counters.get("fleet_requests")
    rep = counters.get("fleet_replies")
    avail = (round(100.0 * rep / req, 2)
             if req and rep is not None else None)
    return {
        "schema": FLEET_AGGREGATE_SCHEMA,
        "kind": "fleet_aggregate",
        "requests": req,
        "replies": rep,
        "failed": counters.get("fleet_failed"),
        "rejected": counters.get("rejected"),
        "routed": counters.get("routed"),
        "failovers": counters.get("failovers"),
        "refused": counters.get("refused"),
        "ejections": counters.get("ejections"),
        "restarts": counters.get("restarts"),
        "kills": counters.get("kills_injected"),
        "availability_pct": avail,
        "segments": _segment_stats(all_spans),
        "events": events,
        "workers": workers,
        # decode tier (ISSUE 17) — additive, schema-stable: always
        # present, None/empty when the inputs carry no decode traffic
        "decode": {
            "requests": counters.get("decode_requests"),
            "replies": counters.get("decode_replies"),
            "failed": counters.get("decode_failed"),
            "migrations": counters.get("decode_migrations"),
            "replays": counters.get("decode_replays"),
        },
        "replica_decode": replica_decode,
        "trace_ids": len(trace_ids),
        "span_count": len(all_spans),
    }
