"""Continuous-batching inference serving tier (ISSUE 7; ROADMAP
item 1 — the "millions of users" leg) + the serving resilience layer
(ISSUE 8: deadlines, retry/backoff with poison isolation, load
shedding, dispatcher supervision, health).

Production traffic is mostly forward passes, and the per-dispatch cost
on an accelerator is dominated by fixed overhead (host dispatch, the
Python framework layer, kernel launch) rather than by the rows in the
batch — so the classic inference-throughput optimization is to turn
many small concurrent requests into a few large fused dispatches.
`ServingEngine` does exactly that:

  admission queue  — `submit()` enqueues a single-sample (or
      small-batch) request into a BOUNDED queue and returns a
      `ServeReply` future; a full queue drops the request LOUDLY
      (`ServeQueueFullError`, counted), never silently stalls the
      caller forever.
  coalescing       — a dispatcher thread drains whatever is waiting,
      up to `max_batch` rows or a `max_wait_ms` deadline from the
      first queued request (the latency/occupancy trade: waiting
      longer fills bigger batches). Requests with different
      per-sample signatures (trailing dims / dtypes) form separate
      dispatch groups in the same drain cycle.
  bucket padding   — the coalesced batch is padded up to the nearest
      PR 6 shape bucket (`export_cache.pad_batch_to_bucket`, the
      `pad_batch`/`batch_mask` idiom: repeat-final-sample rows,
      provably inert for the row-independent eval forward), so
      diverse traffic executes at most `BucketPolicy.n_buckets()`
      distinct programs. A request larger than the top bucket gets a
      loud per-request `BucketOverflowError` — never a silent
      retrace.
  one dispatch     — the padded batch runs through the model's
      forward executable (`model._JitForward` in EVAL mode), which
      loads warm from the AOT export cache when armed: the request
      path never traces on a provisioned worker (native models and
      ONNX-imported `sonnx.SONNXModel`s alike, via
      `topology_fingerprint`). `tools/prewarm.py` populates the store
      offline so worker cold start is deserialize-only.
  scatter          — per-request reply rows are sliced back out
      (pad rows dropped first) and delivered through the futures as
      host numpy arrays.

Resilience (ISSUE 8) — the serving analogue of PR 3's training-side
StepGuard discipline: every failure mode has a bounded, counted,
LOUD recovery path, proven by seed-keyed fault injection:

  deadlines        — `submit(*arrays, deadline_ms=...)` (or the
      `deadline_ms` default knob): a request whose deadline passes
      while still QUEUED is expired before batch assembly — its
      future fails with `ServeDeadlineError`, counted `expired`, and
      the dispatch is never padded with rows nobody is waiting for.
      A request that expires after assembly (mid-dispatch) still
      completes, counted `late`, its reply marked
      `deadline_exceeded=True`.
  retry + poison isolation — a failed fused dispatch retries the
      whole group up to `max_retries` times with exponential backoff
      + seed-keyed jitter (`resilience.backoff_delay_s`); when the
      retries are exhausted the group is BISECTED to isolate poison
      requests — only the requests that fail alone fail their
      futures (`ServePoisonedError`, counted `poisoned`; a terminal
      VERDICT the fleet router never re-submits elsewhere), the rest
      re-dispatch and succeed. One bad input cannot fail a coalesced
      batch of 64.
  load shedding    — beyond the hard `max_queue` drop: a
      `shed_watermark` sheds NEWEST requests with a structured
      `ServeOverloadError` carrying `retry_after_ms` (estimated from
      the rolling dispatch time × queue depth), and `adaptive_wait`
      shrinks the coalesce window toward 0 under sustained depth —
      latency degrades before availability does.
  supervision      — the dispatcher thread runs under a supervisor:
      an unexpected death fails the in-flight futures loudly and
      restarts the loop (bounded by `max_restarts`, counted
      `restarts`); `engine.health()` reports
      `ready`/`degraded`/`unhealthy` with reasons, `health_file`
      snapshots it to disk for fleet probes
      (`tools/serve_health.py` maps state → exit code).
  chaos harness    — `ServingEngine(..., fault_injector=...)` wires a
      seed-keyed `resilience.FaultInjector` through a test-only hook
      in the dispatch path (`dispatch_fail`, `dispatch_hang`,
      `poison_request`, `device_lost_serve`, `dispatcher_kill`); the
      chaos soak in `tests/test_serve_resilience.py` proves no reply
      is ever silently lost and the counters reconcile exactly
      (requests == replies + expired + shed + dropped + overflowed
      + failed).

Observability: per-request spans thread the PR 5 tracer (`queue_wait`
via `trace.record_span` — it crosses threads — plus per-dispatch
`batch_assemble` / `dispatch` / `reply` and per-retry
`dispatch_retry` spans; the decode tier's dispatcher cycle is ten
leaf spans that never overlap — `decode.wait_work`, `decode.admit`,
`decode.{prefill,step}.{assemble,dispatch,readback,scatter}` — around
the `prefill` / `decode_step` records, plus one `decode_queue_wait`
per admitted session), a `MetricsLogger` JSONL stream records one
record per dispatch (batch occupancy, pad fraction, rolling
p50/p95/p99, cumulative expired/shed/retries/failed), and
`cache_stats()["serve"]` exposes queue depth, coalesce sizes, the
bucket hit histogram, and every resilience counter.

Knobs: `device.set_serving(max_batch=..., max_wait_ms=...,
max_queue=...)` and `device.set_serving_resilience(deadline_ms=...,
max_retries=..., backoff_ms=..., shed_watermark=...,
adaptive_wait=..., max_restarts=..., drain_timeout_s=...,
health_file=...)` set the process defaults; `ServingEngine(...)`
overrides per-engine. Speed: the serving cells of `BENCHMARK.json`
(`python3 -m perfbench.run`, on the chip; `PERF.md`).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import export_cache, quant as quant_mod, slo as slo_mod, \
    stats as stats_mod, trace as trace_mod

__all__ = [
    "ServingEngine",
    "ServeReply",
    "ServeQueueFullError",
    "ServeClosedError",
    "ServeDeadlineError",
    "ServeOverloadError",
    "ServeDispatchError",
    "ServePoisonedError",
    "configure",
    "get_config",
    "configure_resilience",
    "get_resilience_config",
    "configure_decode",
    "get_decode_config",
    "prewarm_forward",
    "submit_with_backoff",
    "terminal_counters",
    "TERMINAL_KEYS",
]


class ServeQueueFullError(RuntimeError):
    """The admission queue is at `max_queue`: the request is DROPPED
    (counted in `cache_stats()["serve"]["dropped"]`). Deliberately
    loud at submit time — back-pressure the caller can act on beats a
    queue that grows without bound or a request that silently
    vanishes."""


class ServeClosedError(RuntimeError):
    """The engine is stopped (or stopping): no new requests are
    admitted, and requests still queued at stop() are failed with
    this."""


class ServeDeadlineError(RuntimeError):
    """The request's deadline passed while it was still queued: it was
    expired BEFORE batch assembly (counted `expired`) — nobody was
    going to read the reply, so no dispatch capacity is spent
    producing it. A request that expires after assembly still
    completes (counted `late`, reply marked `deadline_exceeded`)."""


class ServeOverloadError(RuntimeError):
    """The engine is shedding load: queue depth reached the
    `shed_watermark` and the NEWEST request is refused (counted
    `shed`) so already-accepted requests keep their latency. Carries
    `retry_after_ms` — the rolling-dispatch-time × queue-depth
    estimate of when capacity frees up — so callers can back off
    intelligently instead of hammering."""

    def __init__(self, msg: str, retry_after_ms: float):
        super().__init__(msg)
        self.retry_after_ms = float(retry_after_ms)


class ServeDispatchError(RuntimeError):
    """A fused dispatch failed after exhausting `max_retries` retries
    (and, for the isolated requests of a bisected group, failed alone
    too). Wraps the final underlying error; the per-request future
    re-raises this.

    Taxonomy note (ISSUE 13/18): the proc/tcp transport's
    `fleet_proc.ProcTransportError` subclasses this, so a dead worker,
    a missed IPC deadline, or a corrupt frame stream rides the same
    failover path as a local dispatch failure. Frame-level verdicts
    stay on the transport side — `FrameCorruptError` (bad
    magic/version/length/CRC) and its sequence-check refinements
    `FrameReplayError` (duplicated/replayed frame) and `FrameGapError`
    (frames missing/reordered) fail the CONNECTION, and only then
    surface per-request as `ProcTransportError`. During a TCP
    reconnect window the replica sheds with `ServeOverloadError`
    (retry_after_ms) instead: the worker may be coming back, so
    callers back off rather than fail over."""


class ServePoisonedError(ServeDispatchError):
    """Terminal poison VERDICT: the request failed every retry AND
    failed when dispatched alone after group bisection — the input
    itself is bad, not the replica it rode on. Subclasses
    `ServeDispatchError` so existing handlers keep working, but the
    fleet router (`singa_tpu.fleet`) keys on the distinction: a
    `ServeDispatchError` fails over to a different replica, a poison
    verdict NEVER does — the same input would poison every replica in
    turn, and the bisection work would repeat fleet-wide."""


class ServeMigratedError(RuntimeError):
    """The decode session LEFT this engine mid-stream (ISSUE 17):
    `export_decode_sessions()` checkpointed it for live migration and
    failed its local reply with this, carrying the portable checkpoint
    in `.ckpt` (slot KV rows + generated-token ledger + sampling
    config + deadline remainder). Deliberately NOT a
    `ServeDispatchError` subclass — the fleet's failover machinery
    must not treat a planned hand-off as a replica failure; the
    session's stream proxy catches this specifically and resumes the
    checkpoint on another replica (`resume_decode`) with zero token
    loss. A caller holding the raw engine reply sees it loudly: the
    continuation lives elsewhere."""

    def __init__(self, msg: str, ckpt=None):
        super().__init__(msg)
        self.ckpt = ckpt


# ---------------------------------------------------------------------------
# Process-default knobs (user-facing setter: device.set_serving).
# ---------------------------------------------------------------------------
_CONFIG: Dict = {
    # Max ROWS per fused dispatch (the coalescing ceiling). Engines
    # clamp it to the bucket policy's ceiling when one is armed.
    "max_batch": 64,
    # How long the dispatcher waits, from the FIRST queued request,
    # for more requests to coalesce before dispatching a partial
    # batch — the latency floor a lone request pays for occupancy.
    "max_wait_ms": 2.0,
    # Admission-queue bound (requests, not rows). Full => loud drop.
    "max_queue": 4096,
}


def configure(**kw) -> Dict:
    """Update serving defaults (`max_batch`, `max_wait_ms`,
    `max_queue`). User-facing setter: `device.set_serving`."""
    for k, v in kw.items():
        if k not in _CONFIG:
            raise KeyError(f"unknown serving config key {k!r}; known: "
                           f"{sorted(_CONFIG)}")
        if k == "max_wait_ms":
            v = float(v)
            if v < 0:
                raise ValueError("max_wait_ms must be >= 0")
        else:
            v = int(v)
            if v < 1:
                raise ValueError(f"{k} must be >= 1")
        _CONFIG[k] = v
    return dict(_CONFIG)


def get_config() -> Dict:
    return dict(_CONFIG)


# ---------------------------------------------------------------------------
# Resilience knobs (ISSUE 8; user-facing setter:
# device.set_serving_resilience). Engines snapshot these at
# construction — same read-at-build contract as every other knob.
# ---------------------------------------------------------------------------
_RES_CONFIG: Dict = {
    # Default per-request deadline (ms) applied when submit() passes
    # none. None = requests never expire.
    "deadline_ms": None,
    # Dispatch retries after the first attempt (exponential backoff +
    # seed-keyed jitter between attempts). 0 = fail fast to bisection.
    "max_retries": 2,
    # Base backoff before the first retry; doubles per attempt.
    "backoff_ms": 5.0,
    # +/- fraction of deterministic jitter on each backoff delay.
    "backoff_jitter": 0.5,
    # Queue depth at/above which NEW requests shed with
    # ServeOverloadError (None = only the hard max_queue drop).
    "shed_watermark": None,
    # Shrink the coalesce wait toward 0 under sustained queue depth
    # (latency degrades before availability).
    "adaptive_wait": False,
    # Supervised dispatcher restarts before the engine gives up and
    # fails the remaining queue.
    "max_restarts": 3,
    # stop(drain=True) bound: a dispatch hung longer than this stops
    # blocking stop(); remaining futures fail with ServeClosedError.
    "drain_timeout_s": 30.0,
    # Consecutive whole-group dispatch failures before health() turns
    # degraded -> unhealthy.
    "unhealthy_failures": 5,
    # Path for the JSON health snapshot tools/serve_health.py probes
    # (written atomically on every state transition). None = off.
    "health_file": None,
}


def configure_resilience(**kw) -> Dict:
    """Update serving-resilience defaults. User-facing setter:
    `device.set_serving_resilience`."""
    for k, v in kw.items():
        if k not in _RES_CONFIG:
            raise KeyError(
                f"unknown serving resilience key {k!r}; known: "
                f"{sorted(_RES_CONFIG)}")
        if k in ("deadline_ms", "shed_watermark", "drain_timeout_s",
                 "health_file") and v is None:
            pass
        elif k == "deadline_ms":
            v = float(v)
            if v <= 0:
                raise ValueError("deadline_ms must be > 0 (or None)")
        elif k in ("backoff_ms",):
            v = float(v)
            if v < 0:
                raise ValueError(f"{k} must be >= 0")
        elif k == "backoff_jitter":
            v = float(v)
            if not 0.0 <= v <= 1.0:
                raise ValueError("backoff_jitter must be in [0, 1]")
        elif k == "drain_timeout_s":
            v = float(v)
            if v <= 0:
                raise ValueError("drain_timeout_s must be > 0 (or None"
                                 " to wait forever)")
        elif k == "shed_watermark":
            v = int(v)
            if v < 1:
                raise ValueError("shed_watermark must be >= 1")
        elif k == "adaptive_wait":
            v = bool(v)
        elif k == "health_file":
            v = str(v)
        elif k == "unhealthy_failures":
            v = int(v)
            if v < 1:
                raise ValueError("unhealthy_failures must be >= 1")
        else:  # max_retries, max_restarts
            v = int(v)
            if v < 0:
                raise ValueError(f"{k} must be >= 0")
        _RES_CONFIG[k] = v
    return dict(_RES_CONFIG)


def get_resilience_config() -> Dict:
    return dict(_RES_CONFIG)


# ---------------------------------------------------------------------------
# Decode-tier knobs (ISSUE 16; user-facing setter:
# device.set_decode_serving). Engines snapshot these at construction.
# ---------------------------------------------------------------------------
_DECODE_CONFIG: Dict = {
    # KV-slot pool size: how many decode sessions may be in flight at
    # once (waiting-for-prefill + decoding). The pool IS admission
    # control — no free slot => submit_decode sheds with
    # ServeOverloadError + retry_after_ms.
    "max_sessions": 8,
    # Ceiling on per-session max_new_tokens (bounds the slab's seq
    # dim together with the model's max_len).
    "max_new_tokens": 64,
    # Prefills per dispatcher cycle: new sessions prefill in their own
    # dispatches BETWEEN fused decode steps (the prefill/decode
    # split), and this caps how many, so a burst of long prompts
    # never stalls the in-flight decode batch for more than one
    # cycle's worth of prefill work.
    "prefill_batch": 2,
    # Run-ahead ceiling: up to this many fused steps dispatch as ONE
    # scanned program (TransformerLM.decode_scan) when no session
    # joins, leaves, expires, or samples inside the block. 1 disables
    # run-ahead (every token is its own dispatch).
    "decode_block": 8,
}


def configure_decode(**kw) -> Dict:
    """Update decode-serving defaults (`max_sessions`,
    `max_new_tokens`, `prefill_batch`, `decode_block`). User-facing
    setter: `device.set_decode_serving`."""
    for k, v in kw.items():
        if k not in _DECODE_CONFIG:
            raise KeyError(
                f"unknown decode serving key {k!r}; known: "
                f"{sorted(_DECODE_CONFIG)}")
        v = int(v)
        if v < 1:
            raise ValueError(f"{k} must be >= 1")
        _DECODE_CONFIG[k] = v
    return dict(_DECODE_CONFIG)


def get_decode_config() -> Dict:
    return dict(_DECODE_CONFIG)


# ---------------------------------------------------------------------------
# Observability: cache_stats()["serve"]
# ---------------------------------------------------------------------------
class _ServeStats:
    """Counters for the serving tier. `queue_depth` is live state (the
    requests waiting right now); `buckets` is the bucket-size hit
    histogram — together with `coalesce_mean` it says whether traffic
    actually fuses (occupancy near 1 at big buckets) or the wait
    window is too short (many size-1 dispatches).

    Resilience accounting (ISSUE 8): every submitted request ends in
    exactly one terminal bucket — `replies` (delivered, incl. `late`),
    `expired` (deadline passed while queued), `shed` (overload
    watermark), `dropped` (hard queue-full), `overflowed` (above the
    bucket ladder), or `failed` (future failed: dispatch error after
    retries, poison, engine closed) — so
    requests == replies + expired + shed + dropped + overflowed +
    failed holds exactly at quiescence. `errors` stays the legacy
    every-failed-future count (expired + failed + bookkeeping
    errors)."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.requests = 0
        self.replies = 0
        self.errors = 0
        self.dropped = 0
        self.overflowed = 0
        self.dispatches = 0
        self.coalesced_requests = 0
        self.coalesced_rows = 0
        self.pad_rows = 0
        self.max_coalesce = 0
        # resilience counters (ISSUE 8)
        self.expired = 0
        self.late = 0
        self.shed = 0
        self.failed = 0
        self.poisoned = 0
        self.retries = 0
        self.dispatch_failures = 0
        self.restarts = 0
        # queue_depth / effective_wait_ms are LIVE state, not
        # counters — reset keeps them and restarts the high-water
        # mark (the resilience-scaler reset convention).
        self.queue_depth = getattr(self, "queue_depth", 0)
        self.max_queue_depth = self.queue_depth
        self.effective_wait_ms = getattr(self, "effective_wait_ms",
                                         None)
        self._buckets: Dict[int, int] = {}

    def note_dispatch(self, n_requests: int, n_rows: int,
                      n_bucket: int) -> None:
        self.dispatches += 1
        self.coalesced_requests += n_requests
        self.coalesced_rows += n_rows
        self.pad_rows += n_bucket - n_rows
        if n_requests > self.max_coalesce:
            self.max_coalesce = n_requests
        self._buckets[n_bucket] = self._buckets.get(n_bucket, 0) + 1

    def snapshot(self) -> Dict:
        d = max(self.dispatches, 1)
        return {
            "requests": self.requests,
            "replies": self.replies,
            "errors": self.errors,
            "dropped": self.dropped,
            "overflowed": self.overflowed,
            "expired": self.expired,
            "late": self.late,
            "shed": self.shed,
            "failed": self.failed,
            "poisoned": self.poisoned,
            "retries": self.retries,
            "dispatch_failures": self.dispatch_failures,
            "restarts": self.restarts,
            "dispatches": self.dispatches,
            "coalesce_mean": round(self.coalesced_requests / d, 3),
            "max_coalesce": self.max_coalesce,
            "rows": self.coalesced_rows,
            "pad_rows": self.pad_rows,
            "occupancy": round(
                self.coalesced_rows
                / max(self.coalesced_rows + self.pad_rows, 1), 4),
            "queue_depth": self.queue_depth,
            "max_queue_depth": self.max_queue_depth,
            "effective_wait_ms": self.effective_wait_ms,
            "buckets": {str(k): v
                        for k, v in sorted(self._buckets.items())},
        }


_STATS = _ServeStats()
stats_mod.register_cache("serve", _STATS)


def serve_stats() -> _ServeStats:
    return _STATS


# The seven counters of the terminal-outcome reconciliation invariant
# (requests == replies + expired + shed + dropped + overflowed +
# failed at quiescence) — the snapshot a multi-process worker ships in
# its heartbeat/handshake frames (ISSUE 13).
TERMINAL_KEYS = ("requests", "replies", "expired", "shed", "dropped",
                 "overflowed", "failed")


def terminal_counters() -> Dict[str, int]:
    """Serializable snapshot of the terminal counters — what
    `singa_tpu.fleet_worker` puts on the wire so the parent can
    reconcile across the process boundary."""
    return {k: int(getattr(_STATS, k)) for k in TERMINAL_KEYS}


def note_remote_request() -> None:
    """Parent-side mirror for a process-boundary transport
    (`singa_tpu.fleet_proc`): one IPC submit = one request, exactly
    like an in-process `ServingEngine.submit`."""
    _STATS.requests += 1


def note_remote_terminal(kind: str, late: bool = False) -> None:
    """Parent-side mirror of ONE terminal outcome for an IPC request:
    `kind` is a `TERMINAL_KEYS` bucket (or "poisoned", a subset of
    `failed`). The transport guarantees exactly one call per
    `note_remote_request`, which is what keeps the `fleet.reconcile`
    engine-terminals equation exact across the process boundary."""
    if kind == "poisoned":
        _STATS.poisoned += 1
        kind = "failed"
    if kind not in TERMINAL_KEYS or kind == "requests":
        raise ValueError(f"not a terminal bucket: {kind!r}")
    setattr(_STATS, kind, getattr(_STATS, kind) + 1)
    if kind in ("failed", "expired"):
        _STATS.errors += 1  # legacy every-failed-future count
    if late and kind == "replies":
        _STATS.late += 1


_DECODE_TERMINALS = ("completed", "failed", "expired", "shed")


def note_remote_decode_session(resumed: bool = False) -> None:
    """Parent-side mirror of ONE decode-session admission on a remote
    worker (DECODE or RESUME frame ACKed, or refused with overload —
    the worker counts `sessions` in both cases). The parent's decode
    books then obey the same 4-equation reconciliation the worker's
    do, which is what lets `fleet.reconcile` pin it fleet-wide.
    `resumed` mirrors the worker's resumed counter (observability,
    not part of the equation)."""
    dst = stats_mod.decode_stats()
    dst.sessions += 1
    if resumed:
        dst.resumed += 1


def note_remote_decode_terminal(kind: str) -> None:
    """Parent-side mirror of one decode-session terminal: exactly one
    of completed/failed/expired/shed per mirrored admission."""
    if kind not in _DECODE_TERMINALS:
        raise ValueError(f"not a decode terminal bucket: {kind!r}")
    dst = stats_mod.decode_stats()
    setattr(dst, kind, getattr(dst, kind) + 1)


def note_remote_decode_export() -> None:
    """Parent-side mirror of one session EXPORTED off a worker by live
    migration (MIGRATE frame): the worker decremented its `sessions`
    (the session leaves its books without a terminal — it re-admits,
    and re-counts, wherever it resumes), so the parent mirror does
    too."""
    dst = stats_mod.decode_stats()
    dst.sessions -= 1
    dst.migrated += 1


def note_remote_decode_tokens(n: int) -> None:
    """Parent-side mirror of `n` tokens streamed over the wire (TOK
    frames) — observability only; not part of the reconciliation
    equation."""
    stats_mod.decode_stats().tokens_streamed += int(n)


# ---------------------------------------------------------------------------
# Requests / replies
# ---------------------------------------------------------------------------
class ServeReply:
    """Future for one submitted request. `result(timeout)` blocks for
    the reply (host numpy array, or pytree of them, with the request's
    REAL row count) and re-raises the per-request error if the
    dispatch failed — a `BucketOverflowError` request fails ITS future
    loudly without poisoning the batch it would have ridden in.

    `state` tracks the request through the engine —
    `queued` (admitted, waiting; also after a requeue-at-front) →
    `dispatching` (joined a dispatch group; retries/bisection keep it
    here) → `done` / `failed` — so a `result(timeout=...)` that times
    out can tell "still queued" from "dispatch in flight".
    `deadline_exceeded` is True on a delivered reply whose deadline
    passed mid-dispatch (counted `late`)."""

    __slots__ = ("_ev", "_wlock", "_value", "_error", "n", "t_submit",
                 "t_reply", "state", "deadline_exceeded", "_stream",
                 "_stream_cv", "_stream_closed")

    def __init__(self, n: int):
        self._ev = threading.Event()
        self._wlock = threading.Lock()  # serializes the first write
        self._value = None
        self._error: Optional[BaseException] = None
        self.n = n
        self.state = "queued"
        self.deadline_exceeded = False
        self.t_submit = time.perf_counter()
        self.t_reply: Optional[float] = None
        # Incremental token stream (decode-tier replies; ISSUE 16).
        # Forward-tier replies never push — their stream just closes
        # empty at delivery.
        self._stream: List[int] = []
        self._stream_cv = threading.Condition()
        self._stream_closed = False

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._ev.wait(timeout):
            raise TimeoutError(
                f"serve reply not ready (state: {self.state})")
        if self._error is not None:
            raise self._error
        return self._value

    @property
    def latency_s(self) -> Optional[float]:
        return (None if self.t_reply is None
                else self.t_reply - self.t_submit)

    # -- streaming (decode tier) ------------------------------------------
    def tokens(self, timeout: Optional[float] = None):
        """Iterate the session's generated tokens INCREMENTALLY, in
        order, as the decode tier streams them — yields each token id
        (int) as soon as its fused decode step lands, ending when the
        session finishes. A failed session raises its stored error
        AFTER yielding every token that was streamed before the
        failure (the delivered prefix is real — it was produced by
        completed decode steps — only the continuation is lost).
        `timeout` bounds each wait for the NEXT token. The final
        sequence of a completed session is bit-identical to
        `result()`'s trailing `max_new_tokens` column block."""
        i = 0
        while True:
            with self._stream_cv:
                while (i >= len(self._stream)
                       and not self._stream_closed):
                    if not self._stream_cv.wait(timeout):
                        raise TimeoutError(
                            f"no decode token within {timeout}s "
                            f"(state: {self.state})")
                if i < len(self._stream):
                    tok = self._stream[i]
                else:  # closed and drained
                    break
            i += 1
            yield tok
        if self._error is not None:
            raise self._error

    def _push_token(self, tok: int) -> None:
        with self._stream_cv:
            if self._stream_closed:
                # a hung dispatch completing AFTER the reply went
                # terminal (stop()/export timeout) must not extend a
                # stream whose final content is already part of a
                # delivered result or a shipped migration checkpoint —
                # a late push here is exactly how a resumed session
                # would deliver a duplicated token
                return
            self._stream.append(int(tok))
            self._stream_cv.notify_all()

    def _close_stream(self) -> None:
        with self._stream_cv:
            self._stream_closed = True
            self._stream_cv.notify_all()

    # -- engine side -----------------------------------------------------
    def _deliver(self, value) -> bool:
        """First write wins (a hung dispatch completing after stop()
        already failed the future must not flip it). Returns whether
        THIS write won — callers count toward the reconciliation
        invariant only on a win, so a dropped late delivery can't be
        double-counted against the `failed` the stop() path already
        recorded."""
        with self._wlock:  # atomic test-and-set: a delivery and a
            # failure racing (stop()'s drain timeout vs a hung
            # dispatch completing) must produce exactly ONE winner
            if self._ev.is_set():
                return False
            self.t_reply = time.perf_counter()
            self._value = value
            self.state = "done"
            self._ev.set()
        self._close_stream()  # outside _wlock: fixed lock order
        return True

    def _fail(self, err: BaseException) -> bool:
        with self._wlock:
            if self._ev.is_set():
                return False  # first write wins
            self.t_reply = time.perf_counter()
            self._error = err
            self.state = "failed"
            self._ev.set()
        self._close_stream()
        return True


class _Request:
    __slots__ = ("arrays", "n", "sig", "reply", "t_enqueue",
                 "deadline", "poison", "trace")

    def __init__(self, arrays: List[np.ndarray], n: int, sig, reply,
                 deadline: Optional[float] = None, trace=None):
        self.arrays = arrays
        self.n = n
        self.sig = sig
        self.reply = reply
        self.deadline = deadline  # absolute perf_counter time, or None
        self.poison = False  # set by the chaos harness only
        # (trace_id, parent_span_id) inherited from the submitter's
        # trace context (ISSUE 15) — the dispatcher thread stamps this
        # request's spans with it, since the context itself is
        # thread-local to the submitter
        self.trace = trace
        self.t_enqueue = time.perf_counter()


class _DecodeSession:
    """One admitted generative session in the decode tier (ISSUE 16).
    Holds the host-side per-session state the continuous-batching loop
    threads between fused steps: the sampling key at generate()'s
    exact split position, the last sampled token (next step's input),
    the absolute write position, and how many tokens remain. `slot` is
    the session's row in the pooled cache slab (-1 while waiting for
    prefill)."""

    __slots__ = ("prompt", "n_new", "temperature", "top_k", "seed",
                 "reply", "deadline", "trace", "key", "tok", "pos",
                 "left", "slot", "toks", "t_enqueue", "t_last_tok",
                 "idx", "resume_kv", "resumed")

    def __init__(self, prompt: np.ndarray, n_new: int,
                 temperature: float, top_k: int, seed: int, reply,
                 deadline: Optional[float], trace, idx: int):
        self.prompt = prompt            # [1, P] int32
        self.n_new = n_new
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        self.reply = reply
        self.deadline = deadline        # absolute perf_counter, or None
        self.trace = trace              # (trace_id, parent_span_id)
        self.idx = idx                  # per-engine session ordinal
        self.key = None                 # jax PRNG key (set at prefill)
        self.tok = 0                    # last sampled token id
        self.pos = 0                    # next cache write position
        self.left = n_new               # tokens still to produce
        self.slot = -1                  # slab row (-1: not joined yet)
        self.toks: List[int] = []       # produced tokens, in order
        self.t_enqueue = time.perf_counter()
        self.t_last_tok: Optional[float] = None  # TPOT span anchor
        # Migration/resume state (ISSUE 17). `resumed` marks a session
        # admitted via resume_decode with a non-empty ledger: its toks/
        # tok/key were restored at admission, and the prefill path must
        # restore position state instead of sampling a first token.
        # `resume_kv` holds the exported slab rows [L, 2, H, pos, D]
        # when the fast (KV-import) path applies; None means replay
        # (re-prefill prompt + ledger[:-1]).
        self.resume_kv = None
        self.resumed = False


class _DecodeBlock:
    """One fused step or block in flight: what it was given, and what
    the host needs to read it back and to dispatch the next one behind
    it."""

    __slots__ = ("idx", "k", "sampled", "pos", "out", "tok", "counters",
                 "t0")

    def __init__(self, idx: int, k: int, sampled: bool, pos: np.ndarray,
                 out, tok, counters, t0: float):
        self.idx = idx                  # dispatch ordinal (chaos key)
        self.k = k                      # steps
        self.sampled = sampled          # `decode_step`: logits come back
        self.pos = pos                  # [Sb] int32 positions, host
        self.out = out                  # tokens [k, Sb] or logits, device
        self.tok = tok                  # [Sb] token row it ends on, device
        self.counters = counters        # the model's vector, device
        self.t0 = t0                    # dispatch start, perf_counter


def _pow2_ceil(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------
class ServingEngine:
    """Continuous micro-batching over one model's eval forward.

    `model` must have initialized params (call `compile(...)` once) —
    the engine forces EVAL mode at `start()` (serving a train-mode
    forward would consume dropout keys and corrupt BN running stats)
    and dispatches through `model._JitForward`, so the AOT export
    cache, the bucket policy, and the SONNX graph fingerprint all
    apply to the request path exactly as they do to a direct
    `forward_graph` call.

    All dispatching happens on ONE daemon thread: jax dispatch and the
    device RNG key stay single-writer, and `submit()` is safe from any
    number of caller threads. The thread runs under a supervisor
    (`_supervised_loop`): if the loop dies unexpectedly, in-flight
    futures fail loudly and the loop restarts (bounded by
    `max_restarts`).

    `fault_injector` (test-only) wires a `resilience.FaultInjector`
    through the dispatch path — see the module docstring's chaos
    harness notes.
    """

    def __init__(self, model, max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 max_queue: Optional[int] = None,
                 bucket_policy: Optional["export_cache.BucketPolicy"]
                 = None,
                 metrics: Optional["trace_mod.MetricsLogger"] = None,
                 latency_window: int = 2048,
                 deadline_ms: Optional[float] = None,
                 max_retries: Optional[int] = None,
                 backoff_ms: Optional[float] = None,
                 backoff_jitter: Optional[float] = None,
                 shed_watermark: Optional[int] = None,
                 adaptive_wait: Optional[bool] = None,
                 max_restarts: Optional[int] = None,
                 drain_timeout_s: Optional[float] = None,
                 unhealthy_failures: Optional[int] = None,
                 health_file: Optional[str] = None,
                 fault_injector=None,
                 max_sessions: Optional[int] = None,
                 max_new_tokens: Optional[int] = None,
                 prefill_batch: Optional[int] = None,
                 decode_block: Optional[int] = None):
        cfg = get_config()
        res = get_resilience_config()
        dec = get_decode_config()
        self.model = model
        # Tuned-config default load (ISSUE 9): when the autotuner's
        # store (SINGA_TPU_TUNED_STORE / .tuned/) holds a best-known
        # config for this model's topology fingerprint, arm its
        # FORWARD-SAFE subset (BN-stats floor, pallas block envs —
        # never training geometry) before any request traces. A
        # missing store is a silent no-op; a hit logs one stderr line.
        from . import tuning

        self.tuned = tuning.apply_best_for_serving(model)
        self.max_batch = int(max_batch if max_batch is not None
                             else cfg["max_batch"])
        self.max_wait_s = float(max_wait_ms if max_wait_ms is not None
                                else cfg["max_wait_ms"]) / 1e3
        self.max_queue = int(max_queue if max_queue is not None
                             else cfg["max_queue"])
        if self.max_batch < 1 or self.max_queue < 1:
            raise ValueError("max_batch and max_queue must be >= 1")
        # Resilience knobs (per-engine overrides win over the process
        # defaults; None per-engine means "use the default").
        self.deadline_ms = (deadline_ms if deadline_ms is not None
                            else res["deadline_ms"])
        self.max_retries = int(max_retries if max_retries is not None
                               else res["max_retries"])
        self.backoff_s = float(backoff_ms if backoff_ms is not None
                               else res["backoff_ms"]) / 1e3
        self.backoff_jitter = float(backoff_jitter
                                    if backoff_jitter is not None
                                    else res["backoff_jitter"])
        if not 0.0 <= self.backoff_jitter <= 1.0:
            raise ValueError("backoff_jitter must be in [0, 1]")
        self.shed_watermark = (shed_watermark
                               if shed_watermark is not None
                               else res["shed_watermark"])
        if self.shed_watermark is not None and int(
                self.shed_watermark) < 1:
            raise ValueError(
                "shed_watermark must be >= 1 (use None to disable "
                "shedding) — 0 would shed every request on an empty "
                "queue")
        if (self.shed_watermark is not None
                and int(self.shed_watermark) > self.max_queue):
            raise ValueError(
                f"shed_watermark {self.shed_watermark} above max_queue "
                f"{self.max_queue}: the hard drop would always fire "
                "first and the structured overload path never would")
        self.adaptive_wait = bool(adaptive_wait
                                  if adaptive_wait is not None
                                  else res["adaptive_wait"])
        self.max_restarts = int(max_restarts
                                if max_restarts is not None
                                else res["max_restarts"])
        self.drain_timeout_s = (drain_timeout_s
                                if drain_timeout_s is not None
                                else res["drain_timeout_s"])
        self.unhealthy_failures = int(
            unhealthy_failures if unhealthy_failures is not None
            else res["unhealthy_failures"])
        if self.unhealthy_failures < 1:
            raise ValueError("unhealthy_failures must be >= 1")
        self.health_file = (health_file if health_file is not None
                            else res["health_file"])
        self.fault_injector = fault_injector
        # Backoff jitter seed: the injector's seed under test (the
        # chaos runs stay reproducible), else a per-process/per-engine
        # value — a constant here would make every worker in a fleet
        # sleep the same delays and retry in lockstep, which is the
        # thundering herd the jitter exists to break.
        if fault_injector is not None:
            self._jitter_seed = int(getattr(fault_injector, "seed", 0))
        else:
            import os
            self._jitter_seed = (os.getpid() << 20) ^ (id(self)
                                                       & 0xFFFFF)
        # Bucket ladder: an explicit policy wins, else the process
        # policy (device.set_shape_buckets), else a private pow2
        # ladder capped at max_batch — the engine ALWAYS dispatches
        # bucketed shapes, so retraces/artifacts stay bounded even
        # when the process never armed a policy.
        self.policy = (bucket_policy or export_cache.bucket_policy()
                       or export_cache.BucketPolicy(
                           max_batch=_pow2_ceil(self.max_batch)))
        if self.max_batch > self.policy.max_batch:
            raise ValueError(
                f"max_batch {self.max_batch} exceeds the bucket "
                f"ceiling {self.policy.max_batch}; a dispatch the "
                "policy cannot bucket would be a guaranteed overflow")
        # The forward dispatch path re-pads with the PROCESS policy
        # when one is armed — an engine policy with a higher ceiling
        # would coalesce batches the dispatch then rejects, failing
        # whole groups that each passed submit().
        proc = export_cache.bucket_policy()
        if (proc is not None and proc is not self.policy
                and self.policy.bucket_batch(self.max_batch)
                > proc.max_batch):
            raise ValueError(
                f"engine bucket ladder tops at "
                f"{self.policy.bucket_batch(self.max_batch)} but the "
                f"process policy (device.set_shape_buckets) caps "
                f"dispatches at {proc.max_batch}; lower max_batch or "
                "raise the process ceiling")
        self.metrics = metrics
        self._latencies: deque = deque(maxlen=int(latency_window))
        self._queue: deque = deque()
        # THIS engine's live queue depth. The module-global
        # _STATS.queue_depth gauge is last-writer-wins across the N
        # engines a fleet runs in one process — health verdicts and
        # the adaptive-wait EMA must read their OWN engine's depth,
        # or replica A gets judged by replica B's backlog.
        self._depth = 0
        self._lock = threading.Lock()
        self._have_work = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._dispatch_idx = 0
        self._submit_idx = 0  # per-engine submit ordinal (poison key)
        self._attempt_idx = 0  # per dispatch ATTEMPT (retries advance)
        self._cycle_idx = 0  # per coalesce cycle (dispatcher_kill key)
        self._inflight: List[_Request] = []
        self._restarts = 0
        self._consec_failures = 0
        self._depth_ema = 0.0
        self._ema_dispatch_s = 0.0
        self._hung_at_stop = False
        self._health_state: Optional[str] = None
        # Serializes transition detection + the snapshot-file write:
        # a monitoring thread polling health() races the dispatcher's
        # _update_health()/_note_health — without it both see the
        # same change (duplicate transitions) and truncate each
        # other's tmp file mid-write.
        self._health_lock = threading.Lock()
        # (state, reason) tuples, appended whenever the computed
        # health state changes — the unhealthy -> ready transition the
        # acceptance test asserts reads from here.
        self.health_transitions: List = []
        # -- decode tier (ISSUE 16): KV-slot pool + continuous batch --
        self.max_sessions = int(max_sessions if max_sessions is not None
                                else dec["max_sessions"])
        self.decode_max_new = int(max_new_tokens
                                  if max_new_tokens is not None
                                  else dec["max_new_tokens"])
        self.prefill_batch = int(prefill_batch
                                 if prefill_batch is not None
                                 else dec["prefill_batch"])
        self.decode_block = int(decode_block
                                if decode_block is not None
                                else dec["decode_block"])
        if (self.max_sessions < 1 or self.decode_max_new < 1
                or self.prefill_batch < 1 or self.decode_block < 1):
            raise ValueError("max_sessions, max_new_tokens, "
                             "prefill_batch and decode_block must "
                             "be >= 1")
        self._dqueue: deque = deque()       # admitted, awaiting prefill
        self._decode_live: Dict[int, _DecodeSession] = {}  # slot -> sess
        self._decode_reserved = 0  # slots promised = queued + live
        self._decode_lock = threading.Lock()
        self._decode_have_work = threading.Event()
        self._decode_thread: Optional[threading.Thread] = None
        self._decode_running = False
        self._slab = None               # pooled KV cache, built lazily
        self._slab_put = None           # places step inputs beside it
        self._slab_free: List[int] = []  # free slab row indices
        self._decode_params = None
        self._decode_quant = quant_mod.mode()  # frozen at slab build
        self._decode_step_idx = 0       # fused-step ordinal (chaos key)
        self._prefill_idx = 0           # admission ordinal (chaos key)
        self._decode_session_idx = 0
        self._ema_decode_step_s = 0.0   # feeds decode retry_after_ms
        self._decode_tokens_ema = 0.0   # tokens/sec, for health probes

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "ServingEngine":
        if self._running:
            return self
        # Same contract as calling forward_graph directly: the model
        # must have been compile()d (lazy params initialized) first.
        self.model.eval()
        self._running = True
        self._restarts = 0
        self._hung_at_stop = False
        self._thread = threading.Thread(target=self._supervised_loop,
                                        name="singa_tpu-serve",
                                        daemon=True)
        self._thread.start()
        self._update_health()
        return self

    def stop(self, drain: bool = True,
             drain_timeout_s: Optional[float] = None) -> None:
        """Stop the dispatcher. `drain=True` (default) serves what is
        already queued first, but only up to `drain_timeout_s`
        (default: the engine/`set_serving_resilience` knob) — a hung
        dispatch must not block stop() forever; past the timeout the
        remaining futures (queued AND in-flight) fail with
        `ServeClosedError` and the hung daemon thread is abandoned.
        `drain=False` fails queued requests immediately."""
        if not self._running:
            return
        if not drain:
            with self._lock:
                victims = list(self._queue)
                self._queue.clear()
                self._depth = 0
                _STATS.queue_depth = 0
            for req in victims:
                self._fail_request(req, ServeClosedError(
                    "engine stopped"))
        with self._lock:  # atomic vs submit()'s admission check
            self._running = False
        self._have_work.set()  # wake the dispatcher to exit
        t, self._thread = self._thread, None
        if t is not None:
            timeout = (drain_timeout_s if drain_timeout_s is not None
                       else self.drain_timeout_s)
            t.join(timeout)
            if t.is_alive():
                # Hung mid-dispatch: abandon the daemon thread and
                # fail its in-flight futures loudly — a caller blocked
                # on result() must not outwait a dead device. The
                # thread may eventually finish its dispatch; the
                # replies land on already-failed futures and are
                # dropped (first write wins).
                self._hung_at_stop = True
                for req in self._take_inflight():
                    self._fail_request(req, ServeClosedError(
                        f"engine stopped: dispatch still hung after "
                        f"the {timeout}s drain timeout"))
        # Fail any straggler that slipped in while the dispatcher was
        # exiting — a queued request with no thread to serve it would
        # otherwise hang its caller until their own timeout.
        with self._lock:
            victims = list(self._queue)
            self._queue.clear()
            self._depth = 0
            _STATS.queue_depth = 0
        for req in victims:
            self._fail_request(req, ServeClosedError("engine stopped"))
        self._stop_decode(drain_timeout_s)
        self._update_health()

    def _stop_decode(self, drain_timeout_s: Optional[float]) -> None:
        """Tear down the decode tier: stop the decode dispatcher, then
        fail every waiting AND live session with `ServeClosedError`
        (counted `failed` — the 4-equation reconciliation stays exact
        through shutdown) and release their slots and the slab. Mid-stream sessions
        keep the tokens already streamed; only the continuation is
        lost, and loudly."""
        with self._decode_lock:
            self._decode_running = False
        self._decode_have_work.set()
        t, self._decode_thread = self._decode_thread, None
        if t is not None:
            timeout = (drain_timeout_s if drain_timeout_s is not None
                       else self.drain_timeout_s)
            t.join(timeout)
        with self._decode_lock:
            waiting = list(self._dqueue)
            self._dqueue.clear()
            live = list(self._decode_live.values())
            self._decode_live.clear()
            # every session is failed below, so the slab holds nothing
            # a query will read: give its memory back (the next
            # admission builds it again, `_build_slab`)
            self._slab, self._slab_free = None, []
            self._decode_reserved = 0
        dst = stats_mod.decode_stats()
        for s in waiting + live:
            if s.reply._fail(ServeClosedError("engine stopped")):
                dst.failed += 1
                if s.slot >= 0:
                    dst.leaves += 1
        dst.slots_in_use = 0

    def warmup(self, *arrays) -> int:
        """Execute the forward once per dispatchable bucket, padding
        `arrays` (ONE example request) up the pow2 ladder — the
        worker-boot step that moves deserialize + XLA-compile of every
        bucket program off the request path. With a prewarmed store
        this costs loads only (zero traces); without one it traces
        each bucket exactly once, which is the same bounded cost the
        first live requests would otherwise pay at p99. Call before
        (or right after) `start()`, ahead of real traffic — it
        dispatches directly, bypassing the queue. Returns the number
        of bucket programs warmed."""
        from . import tensor as tensor_mod

        batch = [a[:1] for a in self._as_batch(arrays)]
        was_training = self.model.training
        self.model.eval()
        dev = self._device()
        ceiling = min(self.policy.max_batch,
                      _pow2_ceil(self.max_batch))
        warmed, b = 0, 1
        try:
            while b <= ceiling:
                padded = export_cache.pad_batch(batch, b)
                self.model._ensure_forward_exec()(
                    *[tensor_mod.from_numpy(np.ascontiguousarray(a),
                                            device=dev)
                      for a in padded])
                warmed += 1
                b <<= 1
        finally:
            self.model.train(was_training)
        return warmed

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- admission --------------------------------------------------------
    @staticmethod
    def _as_batch(arrays: Sequence) -> List[np.ndarray]:
        out = []
        for a in arrays:
            a = np.asarray(getattr(a, "data", a))
            if a.ndim == 0:
                raise ValueError(
                    "serve requests are batched along dim 0; got a "
                    "0-d input — wrap single samples as shape "
                    "(1, ...)")
            out.append(a)
        return out

    def _estimate_retry_after_ms(self, depth: int) -> float:
        """Overload back-off hint: rolling dispatch seconds × the
        dispatch cycles needed to drain `depth` queued requests. The
        EMA starts at 0 (no dispatch yet) — fall back to the coalesce
        window, the floor any request pays."""
        per_dispatch = self._ema_dispatch_s or self.max_wait_s or 1e-3
        cycles = max(1, -(-depth // max(self.max_batch, 1)))  # ceil
        return max(1.0, round(per_dispatch * cycles * 1e3, 3))

    def submit(self, *arrays, deadline_ms: Optional[float] = None
               ) -> ServeReply:
        """Enqueue one request (numpy arrays or Tensors; every array
        batched along dim 0 with a shared row count) and return its
        `ServeReply` future. `deadline_ms` (default: the engine's
        `deadline_ms` knob) bounds how long the caller will wait:
        still queued past it ⇒ the future fails with
        `ServeDeadlineError` before any dispatch capacity is spent.
        Raises `ServeQueueFullError` / `ServeOverloadError` /
        `ServeClosedError` / `BucketOverflowError` at admission —
        requests the engine could never serve are refused while the
        caller can still act, not parked."""
        if not self._running:
            raise ServeClosedError("engine not running: call start()")
        batch = self._as_batch(arrays)
        if not batch:
            raise ValueError("serve request needs at least one input")
        n = int(batch[0].shape[0])
        for a in batch:
            if int(a.shape[0]) != n:
                raise ValueError(
                    "serve request inputs disagree on the batch dim: "
                    f"{[int(x.shape[0]) for x in batch]}")
        dl = deadline_ms if deadline_ms is not None else self.deadline_ms
        if dl is not None and float(dl) <= 0:
            raise ValueError("deadline_ms must be > 0")
        _STATS.requests += 1
        if n > self.policy.max_batch or n > self.max_batch:
            _STATS.overflowed += 1
            raise export_cache.BucketOverflowError(
                f"request batch {n} exceeds the serving ceiling "
                f"(max_batch {self.max_batch}, top bucket "
                f"{self.policy.max_batch}); split the request or "
                "raise the ceiling — a silent retrace above the "
                "ladder is exactly what the policy forbids")
        if self.policy.seq_dim is not None:
            d = self.policy.seq_dim
            for a in batch:
                if a.ndim > d and int(a.shape[d]) > self.policy.max_seq:
                    _STATS.overflowed += 1
                    raise export_cache.BucketOverflowError(
                        f"request seq length {int(a.shape[d])} (dim "
                        f"{d}) exceeds the bucket ladder's max_seq "
                        f"{self.policy.max_seq}; truncate/split the "
                        "request or raise the ceiling")
        sig = tuple((tuple(int(d) for d in a.shape[1:]),
                     str(a.dtype)) for a in batch)
        reply = ServeReply(n)
        deadline = (None if dl is None
                    else time.perf_counter() + float(dl) / 1e3)
        # Inherit the submitter's trace context (strict None when
        # tracing is off): the parent span is the innermost OPEN span
        # on the submitting thread (the router's `route` span) so the
        # dispatcher-side spans nest under it in the merged timeline.
        ctx = trace_mod.current_trace()
        req_trace = (None if ctx is None else
                     (ctx["trace_id"],
                      trace_mod.current_span_id() or ctx["parent"]))
        req = _Request(batch, n, sig, reply, deadline=deadline,
                       trace=req_trace)
        inj = self.fault_injector
        if inj is not None:
            # keyed by the per-ENGINE submit ordinal (1-based), so a
            # schedule like {"poison_request": {3}} marks this
            # engine's 3rd request regardless of process history
            with self._lock:
                self._submit_idx += 1
                idx = self._submit_idx
            if inj.should("poison_request", idx):
                req.poison = True
        with self._lock:
            # re-checked under the lock stop() takes: past this point
            # the dispatcher is guaranteed to drain the queue once
            # more before exiting, so the request cannot strand
            if not self._running:
                # the future was never enqueued: fail it too so the
                # terminal-outcome reconciliation stays exact even
                # for submits racing stop(). `counted=True` marks
                # that THIS refusal bumped requests+failed (the
                # pre-admission ServeClosedError above counted
                # nothing) — the fleet router's attempt accounting
                # needs the distinction to stay exact.
                err = ServeClosedError("engine stopped")
                err.counted = True
                self._fail_request(req, err)
                raise err
            depth = len(self._queue)
            if (self.shed_watermark is not None
                    and depth >= int(self.shed_watermark)):
                # Shed the NEWEST request: already-accepted requests
                # keep their latency; this caller gets a structured
                # back-off hint instead of a collapsing queue.
                _STATS.shed += 1
                raise ServeOverloadError(
                    f"shedding load: queue depth {depth} at the "
                    f"shed watermark ({self.shed_watermark}); retry "
                    "after the hinted backoff",
                    retry_after_ms=self._estimate_retry_after_ms(
                        depth))
            if depth >= self.max_queue:
                _STATS.dropped += 1
                raise ServeQueueFullError(
                    f"admission queue full ({self.max_queue} "
                    "requests); the request was dropped — scale "
                    "workers or raise max_queue "
                    "(device.set_serving)")
            self._queue.append(req)
            self._depth = len(self._queue)
            _STATS.queue_depth = self._depth
            if _STATS.queue_depth > _STATS.max_queue_depth:
                _STATS.max_queue_depth = _STATS.queue_depth
        self._have_work.set()
        return reply

    def infer(self, *arrays, timeout: Optional[float] = None,
              deadline_ms: Optional[float] = None):
        """Synchronous submit+wait — one request's reply."""
        return self.submit(*arrays,
                           deadline_ms=deadline_ms).result(timeout)

    # -- decode tier: admission (ISSUE 16) --------------------------------
    def _estimate_decode_retry_ms(self) -> float:
        """Overload back-off hint for a shed decode session: rolling
        fused-step seconds × the fewest remaining tokens of any live
        session — the earliest a slot can free. Called under
        `_decode_lock`."""
        per = self._ema_decode_step_s or self.max_wait_s or 1e-3
        left = min((s.left for s in self._decode_live.values()),
                   default=1)
        return max(1.0, round(per * max(1, left) * 1e3, 3))

    def submit_decode(self, prompt_ids, max_new_tokens: int,
                      temperature: float = 0.0, top_k: int = 0,
                      seed: int = 0,
                      deadline_ms: Optional[float] = None) -> ServeReply:
        """Enqueue one generative session (prompt [P] or [1, P] int
        ids, extended by `max_new_tokens`) and return its `ServeReply`.
        `reply.tokens()` streams each generated token as its fused
        decode step lands; `reply.result()` blocks for the full
        [1, P + max_new_tokens] array, bit-identical to
        `model.generate()` with the same sampling config and seed.

        Admission control IS the KV-slot pool: the engine holds
        `max_sessions` cache slots, and a session is admitted only by
        reserving one — queued + live sessions never exceed the pool,
        so decode memory is bounded by construction. No free slot ⇒
        `ServeOverloadError` with `retry_after_ms` (rolling step time ×
        the soonest-finishing session), counted `shed` in
        `cache_stats()["decode"]`. The slot frees on finish, expiry,
        failure, or stop() — every admitted session lands in exactly
        one of completed/failed/expired, and with shed the four
        buckets reconcile: sessions == completed+failed+expired+shed.
        """
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None, :]
        if prompt.ndim != 2 or prompt.shape[0] != 1:
            raise ValueError(
                f"decode prompt must be [P] or [1, P] token ids, got "
                f"shape {prompt.shape} — sessions are single-sequence; "
                "the engine fuses them across slots itself")
        P = int(prompt.shape[1])
        n_new = int(max_new_tokens)
        if P < 1:
            raise ValueError("decode prompt must be non-empty")
        if n_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if n_new > self.decode_max_new:
            raise ValueError(
                f"max_new_tokens {n_new} exceeds the engine ceiling "
                f"{self.decode_max_new} (device.set_decode_serving)")
        model_max = int(getattr(self.model, "max_len", 0) or 0)
        if model_max and P + n_new > model_max:
            raise ValueError(
                f"prompt {P} + max_new_tokens {n_new} exceeds the "
                f"model's max_len {model_max}")
        dl = deadline_ms if deadline_ms is not None else self.deadline_ms
        if dl is not None and float(dl) <= 0:
            raise ValueError("deadline_ms must be > 0")
        deadline = (None if dl is None
                    else time.perf_counter() + float(dl) / 1e3)
        ctx = trace_mod.current_trace()
        sess_trace = (None if ctx is None else
                      (ctx["trace_id"],
                       trace_mod.current_span_id() or ctx["parent"]))
        dst = stats_mod.decode_stats()
        with self._decode_lock:
            # re-checked under the lock _stop_decode takes: past this
            # point stop() is guaranteed to drain the decode queue
            # once more, so an admitted session cannot strand
            if not self._running:
                raise ServeClosedError(
                    "engine not running: call start()")
            dst.sessions += 1
            dst.slots = self.max_sessions
            if self._decode_reserved >= self.max_sessions:
                dst.shed += 1
                raise ServeOverloadError(
                    f"decode slot pool exhausted ({self.max_sessions} "
                    "sessions reserved); retry after the hinted "
                    "backoff",
                    retry_after_ms=self._estimate_decode_retry_ms())
            self._decode_reserved += 1
            self._decode_session_idx += 1
            reply = ServeReply(1)
            sess = _DecodeSession(prompt, n_new, float(temperature),
                                  int(top_k), int(seed), reply,
                                  deadline, sess_trace,
                                  self._decode_session_idx)
            self._dqueue.append(sess)
            need_thread = self._decode_thread is None
            if need_thread:
                self._decode_running = True
                self._decode_thread = threading.Thread(
                    target=self._decode_supervised_loop,
                    name="singa_tpu-serve-decode", daemon=True)
                self._decode_thread.start()
        self._decode_have_work.set()
        return reply

    def warm_decode(self, prompt_lens=(), max_new_tokens=None,
                    samplers=()) -> int:
        """Pre-compile (or AOT-load, when the export_cache store is
        armed) every decode-tier executable this engine can dispatch:
        the fused `decode_step` (a sampled session's logits), its
        greedy token program `decode_scan(k=1)`, each pow2
        `decode_scan` rung up to
        `decode_block`, and a cohort prefill per (batch rung up to
        `prefill_batch`, prompt bucket). Continuous batching admits
        sessions MID-STREAM, so the first-ever cohort size or
        run-ahead rung would otherwise pay its compile inside live
        sessions' latency budget — call this before offering traffic.
        `prompt_lens` are the raw prompt lengths expected (bucketed
        exactly like submit_decode buckets them); `max_new_tokens`
        sizes the slab's sequence rung (defaults to the engine
        ceiling); `samplers` is the (temperature, top_k) pairs
        sampled traffic will use — `model.sample_fn` compiles per
        pair, and an unwarmed pair lands its compile inside the first
        sampled session's TTFT. Warm dispatches run real (cheap)
        programs against the pooled slab and discard the results —
        pad prefill rows carry an out-of-bounds slot, so nothing is
        written. Returns the number of executables warmed."""
        import jax

        n_new = int(max_new_tokens if max_new_tokens is not None
                    else self.decode_max_new)
        pol = self.policy

        def bseq(n):
            return (pol.bucket_seq(n)
                    if pol.max_seq is not None and n <= pol.max_seq
                    else _pow2_ceil(n))

        pbs = sorted({bseq(max(1, int(p))) for p in prompt_lens})
        if not pbs:
            pbs = [bseq(1)]
        need_t = max(pbs) + n_new
        with self._decode_lock:
            if self._slab is None:
                geom = self._build_slab(need_t)
            elif need_t > self._slab_dims()[1]:
                geom = self._grow_slab(need_t)
            else:
                geom = self._decode_geom()
        params = geom[0]
        model = self.model
        Sb = self._slab_dims()[0]
        warmed = 0
        put = self._slab_put
        tok = put(np.zeros(Sb, np.int32))
        pos = put(np.zeros(Sb, np.int32))
        # every program donates the slab and leaves only the one it
        # returns; what the warm steps wrote there is stale state no
        # query attends (prefill_slab's argument)
        def ran(out, slab):
            np.asarray(out)
            model.take_step_counters()
            self._slab = slab
            return out

        lg = ran(*model.decode_step(params, self._slab, tok, pos))
        warmed += 1
        for t_k in samplers:
            t, k = float(t_k[0]), int(t_k[1])
            if t == 0.0:
                continue  # greedy is `decode_scan`'s argmax, warmed below
            key, sub = jax.random.split(jax.random.PRNGKey(0))
            np.asarray(model.sample_fn(t, k)(lg[0:1], sub))
            warmed += 1
        ks = {1}    # the token program of a single greedy step
        k = 2
        while k <= self.decode_block:
            ks.add(k)
            k <<= 1
        if self.decode_block > 1:
            ks.add(self.decode_block)  # its own rung when not pow2
        for k in sorted(ks):
            ran(*model.decode_scan(params, self._slab, tok, pos, k))
            warmed += 1
        bmax = min(self.prefill_batch, Sb)
        bmax = (pol.bucket_batch(bmax) if bmax <= pol.max_batch
                else _pow2_ceil(bmax))
        bb = 1
        while bb <= bmax:
            for pb in pbs:
                ids = put(np.zeros((bb, pb), np.int32))
                nv = put(np.ones(bb, np.int32))
                sv = put(np.full(bb, Sb, np.int32))  # OOB: writes
                # nothing
                ran(*model.prefill_slab(params, self._slab, ids, nv,
                                        sv))
                warmed += 1
            bb <<= 1
        return warmed

    # -- decode tier: live migration (ISSUE 17) ---------------------------
    def export_decode_sessions(self) -> List[Dict]:
        """Checkpoint every in-flight decode session OFF this engine
        for live migration. Stops the decode dispatcher (it restarts
        lazily on the next admission — the forward tier keeps
        serving), snapshots each queued + live session into a portable
        checkpoint (prompt, generated-token ledger, sampling config +
        seed — the PRNG key schedule re-derives from these two —
        deadline remainder, and the slot's exported KV rows for live
        sessions), fails the local reply with `ServeMigratedError`
        carrying the checkpoint, and returns the checkpoints.

        Counters: each exported session decrements `sessions` and
        counts `migrated` — it left these books without a terminal and
        will be re-admitted (re-counted) wherever it resumes, so the
        4-equation reconciliation stays exact on BOTH engines. A
        session whose deadline already passed is expired here instead
        of shipped (nobody should pay migration for a dead session).

        If the decode dispatcher is HUNG mid-step past the drain
        timeout, live sessions export WITHOUT their KV (ledger replay
        on the target) — the slab may be mid-write (or, donated to the
        program that hangs, deleted) and a torn KV row is exactly the
        corruption migration must never ship; correctness first, the
        KV transplant is only the fast path.
        Checkpoint leaves are numpy arrays / scalars / None only, so
        the dict crosses `fleet_proc.encode_tree` unchanged."""
        with self._decode_lock:
            self._decode_running = False
        self._decode_have_work.set()
        t, self._decode_thread = self._decode_thread, None
        hung = False
        if t is not None:
            t.join(self.drain_timeout_s)
            hung = t.is_alive()
        dst = stats_mod.decode_stats()
        model = self.model
        now = time.perf_counter()
        with self._decode_lock:
            waiting = list(self._dqueue)
            self._dqueue.clear()
            live = sorted(self._decode_live.items())
            self._decode_live.clear()
            slab = self._slab
            if slab is not None:
                self._slab_free = list(range(self._slab_dims()[0]))
                if self._slab_lost():
                    # a dispatcher hung (or died) inside a program
                    # holds the donated slab: nothing to export, every
                    # session goes by replay, the next one finds a slab
                    self._rebuild_lost_slab()
                    slab = None
            self._decode_reserved = 0
            dst.slots_in_use = 0
        out: List[Dict] = []
        for slot, sess in list(live) + [(-1, s) for s in waiting]:
            # snapshot the ledger ONCE; position state derives from it
            # (a hung dispatcher may still be mutating sess.pos)
            toks = list(sess.toks)
            had_slot = slot >= 0
            sess.slot = -1
            rem = None
            if sess.deadline is not None:
                rem = (sess.deadline - now) * 1e3
                if rem <= 0:
                    if sess.reply._fail(ServeDeadlineError(
                            "decode session expired at migration "
                            f"with {sess.left} of {sess.n_new} "
                            "tokens left")):
                        dst.expired += 1
                    if had_slot:
                        dst.leaves += 1
                    continue
            kv = None
            if had_slot and toks and not hung and slab is not None:
                kv = model.export_slab_rows(
                    slab, slot, int(sess.prompt.shape[1]) + len(toks) - 1)
            elif sess.resume_kv is not None:
                kv = sess.resume_kv  # queued resume: pass it through
            ckpt = {
                "prompt": sess.prompt,
                "toks": np.asarray(toks, np.int32),
                "n_new": sess.n_new,
                "temperature": sess.temperature,
                "top_k": sess.top_k,
                "seed": sess.seed,
                "deadline_ms_left": rem,
                "kv": kv,
            }
            if isinstance(kv, tuple):
                # int8 slab (ISSUE 19): ship the PACKED pair as two
                # plain numpy leaves — "kv" keeps its shape[3]==pos
                # accessor (now int8, ~4x fewer bytes on the wire)
                # and "kv_scale" carries the [L, 2, pos] scale plane
                ckpt["kv"], ckpt["kv_scale"] = kv[0], kv[1]
            if sess.reply._fail(ServeMigratedError(
                    f"decode session migrated mid-stream "
                    f"({len(toks)} of {sess.n_new} tokens produced); "
                    "the continuation resumes elsewhere", ckpt=ckpt)):
                dst.sessions -= 1
                dst.migrated += 1
                if had_slot:
                    dst.leaves += 1
                out.append(ckpt)
        return out

    def resume_decode(self, ckpt: Dict) -> ServeReply:
        """Admit a migrated session's checkpoint mid-stream and return
        a fresh `ServeReply` whose stream re-plays the ledger prefix
        first (consumers that dedupe by count — the fleet's stream
        proxy — see no tear and no duplicate) and then continues
        bit-identically to the original `generate()`: the PRNG key is
        re-derived by replaying `len(toks)` splits from the seed, and
        the KV state either transplants directly (`ckpt["kv"]`, the
        fast path) or rebuilds by re-prefilling prompt + ledger[:-1]
        (the replay path — correctness does not depend on the
        checkpoint's KV). Counts as a NEW admission (`sessions` +
        `resumed`; overload at admission counts `shed` exactly like
        `submit_decode`) — the exporter already took the session off
        its own books."""
        import jax

        prompt = np.asarray(ckpt["prompt"], np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None, :]
        raw = ckpt.get("toks")
        toks = ([] if raw is None
                else [int(x) for x in np.asarray(raw).ravel()])
        n_new = int(np.asarray(ckpt["n_new"]))
        temperature = float(np.asarray(ckpt.get("temperature", 0.0)))
        top_k = int(np.asarray(ckpt.get("top_k", 0)))
        seed = int(np.asarray(ckpt.get("seed", 0)))
        rem = ckpt.get("deadline_ms_left")
        kv = ckpt.get("kv")
        kv_scale = ckpt.get("kv_scale")
        if kv is not None and kv_scale is not None:
            # packed int8 checkpoint: rebuild the (payload, scale)
            # pair import_slab_rows transplants
            kv = (np.asarray(kv, np.int8),
                  np.asarray(kv_scale, np.float32))
        P = int(prompt.shape[1])
        k0 = len(toks)
        if P < 1 or n_new < 1 or k0 > n_new:
            raise ValueError(
                f"malformed decode checkpoint: P={P}, n_new={n_new}, "
                f"ledger={k0}")
        deadline = (None if rem is None
                    else time.perf_counter()
                    + float(np.asarray(rem)) / 1e3)
        ctx = trace_mod.current_trace()
        sess_trace = (None if ctx is None else
                      (ctx["trace_id"],
                       trace_mod.current_span_id() or ctx["parent"]))
        dst = stats_mod.decode_stats()
        if k0 >= n_new:
            # already complete (defensive: finished sessions retire
            # before export) — deliver the full sequence immediately
            reply = ServeReply(1)
            for t_ in toks:
                reply._push_token(t_)
            dst.sessions += 1
            dst.resumed += 1
            if reply._deliver(np.concatenate(
                    [prompt, np.asarray([toks], np.int32)], axis=1)):
                dst.completed += 1
            return reply
        key = None
        if temperature != 0.0 and k0 > 0:
            # generate()'s exact schedule: one split per produced
            # token, next-key half kept — replayed from the seed
            key = jax.random.PRNGKey(seed)
            for _ in range(k0):
                key, _ = jax.random.split(key)
        # the ledger re-streams through the NEW reply BEFORE the
        # session can reach the dispatcher: a consumer that skips the
        # first k0 tokens (the stream proxy) observes one seamless,
        # gapless stream — ledger first, then live continuation
        reply = ServeReply(1)
        for t_ in toks:
            reply._push_token(t_)
        with self._decode_lock:
            if not self._running:
                raise ServeClosedError(
                    "engine not running: call start()")
            dst.sessions += 1
            dst.slots = self.max_sessions
            if self._decode_reserved >= self.max_sessions:
                dst.shed += 1
                raise ServeOverloadError(
                    f"decode slot pool exhausted ({self.max_sessions} "
                    "sessions reserved); resume elsewhere or retry "
                    "after the hinted backoff",
                    retry_after_ms=self._estimate_decode_retry_ms())
            self._decode_reserved += 1
            self._decode_session_idx += 1
            sess = _DecodeSession(prompt, n_new, temperature, top_k,
                                  seed, reply, deadline, sess_trace,
                                  self._decode_session_idx)
            if k0:
                sess.resumed = True
                sess.toks = list(toks)
                sess.tok = toks[-1]
                sess.key = key
                if kv is not None:
                    sess.resume_kv = (kv if isinstance(kv, tuple)
                                      else np.asarray(kv))
            dst.resumed += 1
            self._dqueue.append(sess)
            need_thread = self._decode_thread is None
            if need_thread:
                self._decode_running = True
                self._decode_thread = threading.Thread(
                    target=self._decode_supervised_loop,
                    name="singa_tpu-serve-decode", daemon=True)
                self._decode_thread.start()
        self._decode_have_work.set()
        return reply

    # -- decode tier: the continuous-batching dispatcher ------------------
    def _slab_seq_bucket(self, need_t: int) -> int:
        """Sequence-dim bucket for the pooled slab: the PR 6 pow2
        ladder (`policy.bucket_seq`), capped at the model's max_len
        ceiling. Every rung is a power of two — the property that
        keeps slab rows bitwise identical to `generate()` at ANY rung
        (see `TransformerLM.generate`'s cache comment), so the slab
        can start small and climb the ladder as longer sessions
        arrive instead of paying max_len memory traffic per step."""
        cap = _pow2_ceil(int(self.model.max_len))
        pol = self.policy
        if pol.max_seq is not None and need_t <= pol.max_seq:
            return min(pol.bucket_seq(need_t), cap)
        return min(_pow2_ceil(max(1, int(need_t))), cap)

    def _slab_dims(self):
        """(slots, sequence rung) of the live slab, as the model
        states them: the slab's layout is the model's."""
        return self.model.slab_dims(self._slab)

    def _decode_geom(self):
        """(params, slots, sequence rung) of the live slab."""
        return (self._decode_params, *self._slab_dims())

    def _build_slab(self, need_t: int):
        """Allocate the pooled cache: the model states its layout
        (`new_slab`: which layers hold the context and climb the
        sequence ladder, which a ring that does not), the engine says
        how many slots and which rung. One slot a session
        (`max_sessions`: a slot past it would never be admitted, and
        a long-context slab padded to the next power of two leaves no
        room for the weights); the sequence dim starts at the smallest ladder rung covering
        `need_t` and grows via `_grow_slab`. Returns
        (params, slots, sequence rung)."""
        import jax

        model = self.model
        # int8 decode tier (ISSUE 19): the quant mode is FROZEN at
        # slab build — params, slab form, and every warmed executable
        # must agree for the session's whole life (a mid-stream flip
        # would orphan the slab); flip the knob, drain, rebuild.
        self._decode_quant = (
            "int8" if quant_mod.enabled()
            and hasattr(model, "_decode_params_quant") else "off")
        params = (model._decode_params_quant()
                  if self._decode_quant == "int8"
                  else model._decode_params())
        # every decode-tier call receives this tree: a leaf that is
        # not a device array is transferred again on each one
        dst = stats_mod.decode_stats()
        dst.host_leaves_per_call = sum(
            not isinstance(leaf, jax.Array)
            for leaf in jax.tree_util.tree_leaves(params))
        Sb = self.max_sessions
        Tslab = self._slab_seq_bucket(need_t)
        # born ON the engine's device, and every step input placed
        # beside it (`_slab_put`): an uncommitted jnp.zeros/asarray
        # lands on jax's default device (chip 0), which on a multi-chip
        # host is not where replica i's params live — and it gives the
        # live path (committed slab out of the previous step) another
        # jit signature than the one warm_decode compiled
        device = self._device()
        self._slab_put = device.put
        self._slab = model.new_slab(params, Sb, Tslab, device.jax_device)
        self._note_slab_bytes(dst)
        self._slab_free = list(range(Sb))
        self._decode_params = params
        return params, Sb, Tslab

    def _note_slab_bytes(self, dst) -> None:
        """A gauge for each kind of entry the model states its slab
        has; a kind this model does not state reads 0."""
        by_kind = self.model.slab_bytes(self._slab)
        dst.cache_bytes = {kind: int(by_kind.get(kind, 0))
                           for kind in {**dst.cache_bytes, **by_kind}}

    def _grow_slab(self, need_t: int):
        """Climb the sequence ladder mid-stream: the model pads what
        holds the context out to the next rung covering `need_t` (a
        ring stays as it is). Live rows carry their state across the
        copy unchanged, and because every rung is pow2 their remaining
        tokens still decode bit-identically to `generate()` — growth
        is invisible to in-flight streams. Returns the refreshed
        geometry."""
        old_t = self._slab_dims()[1]
        new_t = self._slab_seq_bucket(need_t)
        if new_t > old_t:
            self._slab = self.model.grow_slab(self._slab, new_t)
            self._note_slab_bytes(stats_mod.decode_stats())
        return self._decode_geom()

    def _slab_lost(self) -> bool:
        """A program donates the slab, so one that fails after its
        dispatch leaves its input deleted: there is nothing to retry
        from. (A failure before dispatch leaves the slab as it was.)"""
        import jax

        return any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(self._slab))

    def _rebuild_lost_slab(self, lost: bool = False) -> None:
        """Fresh buffers at the lost slab's geometry (its sessions
        have been failed by the caller), so queued work can go on:
        where a failed program took it, or where `lost` says that a
        program that failed after its dispatch wrote it."""
        if lost or self._slab_lost():
            slots, seq = self._slab_dims()
            self._slab = self.model.new_slab(
                self._decode_params, slots, seq,
                self._device().jax_device)

    def _fail_live_if_slab_lost(self, dst, what: str, e) -> None:
        """After a failed `what` (a prefill, an import) that is not
        the live sessions' own program: if it took the donated slab
        with it, their state is gone too, so fail them and rebuild."""
        if not self._slab_lost():
            return
        self._rebuild_lost_slab()
        with self._decode_lock:
            live = list(self._decode_live.values())
        for sess in live:
            self._decode_fail_session(sess, dst, ServeDispatchError(
                f"the donated slab was lost to a failed {what}: {e!r}"))

    def _decode_free_slot(self, sess: "_DecodeSession") -> None:
        """Return a session's slab row to the pool (lowest-index-first
        reuse keeps slot assignment deterministic under a seeded
        schedule). Called under `_decode_lock`."""
        if sess.slot >= 0:
            self._decode_live.pop(sess.slot, None)
            self._slab_free.append(sess.slot)
            self._slab_free.sort()
            sess.slot = -1
        self._decode_reserved -= 1

    def _decode_finish(self, sess: "_DecodeSession", dst) -> None:
        """Retire a finished session: deliver the full sequence (the
        exact array `generate()` returns) and free the slot."""
        out = np.concatenate(
            [sess.prompt, np.asarray([sess.toks], np.int32)], axis=1)
        if sess.reply._deliver(out):
            dst.completed += 1
        dst.retires += 1
        if sess.slot >= 0:
            dst.leaves += 1
        with self._decode_lock:
            self._decode_free_slot(sess)

    def _decode_fail_session(self, sess: "_DecodeSession", dst,
                             err: BaseException,
                             expired: bool = False) -> None:
        """Terminal decode failure: exactly one of expired/failed per
        session (first write wins), slot freed either way."""
        if sess.reply._fail(err):
            if expired:
                dst.expired += 1
            else:
                dst.failed += 1
        if sess.slot >= 0:
            dst.leaves += 1
        with self._decode_lock:
            self._decode_free_slot(sess)

    def _decode_expire(self, dst) -> None:
        """Expire sessions whose deadline passed — queued (before any
        prefill capacity is spent) AND live mid-stream (the slot frees
        for queued work; the streamed prefix stays delivered)."""
        now = time.perf_counter()
        victims: List[_DecodeSession] = []
        with self._decode_lock:
            for sess in list(self._dqueue):
                if sess.deadline is not None and now >= sess.deadline:
                    self._dqueue.remove(sess)
                    victims.append(sess)
            for sess in list(self._decode_live.values()):
                if sess.deadline is not None and now >= sess.deadline:
                    victims.append(sess)
        for sess in victims:
            self._decode_fail_session(sess, dst, ServeDeadlineError(
                f"decode session expired after "
                f"{(now - sess.t_enqueue) * 1e3:.1f} ms with "
                f"{sess.left} of {sess.n_new} tokens left"),
                expired=True)

    def _decode_supervised_loop(self) -> None:
        """`_decode_loop` under the same supervisor discipline as the
        forward dispatcher: an escaping exception fails the LIVE
        sessions loudly (their slab rows may be mid-step) and restarts
        the loop, bounded by `max_restarts`."""
        dst = stats_mod.decode_stats()
        while True:
            try:
                self._decode_loop()
                return  # clean exit (stop())
            except BaseException as e:  # noqa: BLE001 — supervisor
                with self._decode_lock:
                    live = list(self._decode_live.values())
                for sess in live:
                    self._decode_fail_session(sess, dst,
                                              ServeDispatchError(
                        f"decode dispatcher died mid-stream: {e!r}"))
                _STATS.restarts += 1
                self._restarts += 1
                if not self._decode_running:
                    return
                if self._restarts > self.max_restarts:
                    with self._decode_lock:
                        self._decode_running = False
                        waiting = list(self._dqueue)
                        self._dqueue.clear()
                    for sess in waiting:
                        self._decode_fail_session(sess, dst,
                                                  ServeClosedError(
                            f"decode dispatcher restarts exhausted "
                            f"({self.max_restarts})"))
                    return

    def _decode_loop(self) -> None:
        """Token-granularity continuous batching: every cycle expires
        stale sessions, admits up to `prefill_batch` queued sessions
        through ONE fused cohort prefill dispatch (bounded, so a burst
        of prompts never stalls the decode batch for long), then
        advances EVERY live session one token with ONE fused
        `decode_step` over the pooled slab (or a run-ahead block, and
        the blocks chained behind it while nothing can join or leave:
        `_decode_fused_step`) — sequences join and leave the fused
        batch between steps, and a freed slot re-admits queued work
        mid-stream."""
        dst = stats_mod.decode_stats()
        dst.slots = self.max_sessions
        geom = None
        while True:
            with self._decode_lock:
                has_work = bool(self._dqueue or self._decode_live)
                running = self._decode_running
            if not running:
                return  # stop() fails the remaining sessions
            if not has_work:
                with trace_mod.span("decode.wait_work"):
                    self._decode_have_work.wait(0.05)
                    self._decode_have_work.clear()
                continue
            with trace_mod.span("decode.admit"):
                self._decode_expire(dst)
                # -- resume fast path: transplant migrated KV rows
                # first (a resumed session re-joins WITHOUT a prefill
                # dispatch)
                if self._decode_admit_imports(dst):
                    geom = self._decode_geom()
                # -- admit: ONE cohort prefill dispatch, bounded per
                # cycle
                cohort = []
                while len(cohort) < self.prefill_batch:
                    with self._decode_lock:
                        if not self._dqueue:
                            break
                        head = self._dqueue[0]
                        if head.resume_kv is not None:
                            # a KV import can't ride the prefill
                            # program; it waits for the next cycle's
                            # import pass
                            break
                        P_h = self._prefill_len(head)
                        pol = self.policy
                        Pb_h = (pol.bucket_seq(P_h)
                                if pol.max_seq is not None
                                and P_h <= pol.max_seq
                                else _pow2_ceil(P_h))
                        need_t = max(
                            int(head.prompt.shape[1]) + head.n_new,
                            Pb_h)
                        if self._slab is None:
                            geom = self._build_slab(need_t)
                        elif need_t > self._slab_dims()[1]:
                            geom = self._grow_slab(need_t)
                        if not self._slab_free:
                            break
                        sess = self._dqueue.popleft()
                        slot = self._slab_free.pop(0)
                        self._prefill_idx += 1
                        ordinal = self._prefill_idx
                    # waited for a slot and for its turn; its prefill
                    # is the `prefill` span that follows
                    trace_mod.record_span(
                        "decode_queue_wait", sess.t_enqueue,
                        time.perf_counter(), trace=sess.trace)
                    cohort.append((sess, slot, ordinal))
            if cohort:
                if geom is None:
                    geom = self._decode_geom()
                self._decode_prefill(cohort, geom, dst)
            # -- one fused decode step over every live slot
            with self._decode_lock:
                live = sorted(self._decode_live.items())
            if not live:
                continue
            if geom is None:
                geom = self._decode_geom()
            self._decode_fused_step(live, geom, dst)

    @staticmethod
    def _prefill_len(sess: "_DecodeSession") -> int:
        """How many token ids this session's prefill runs: the prompt,
        plus — for a ledger REPLAY resume — every produced token
        except the last (which is the next step's input, exactly where
        the original stream stood)."""
        P = int(sess.prompt.shape[1])
        if sess.resumed and len(sess.toks) > 1:
            return P + len(sess.toks) - 1
        return P

    def _decode_admit_imports(self, dst) -> bool:
        """Admit queued KV-import resumes (head-of-queue order, like
        every other admission): size the slab for each, take a free
        slot, and transplant the exported rows — no prefill dispatch.
        Returns whether anything joined (the caller refreshes its
        cached geometry)."""
        any_in = False
        while True:
            with self._decode_lock:
                if (not self._dqueue
                        or self._dqueue[0].resume_kv is None):
                    break
                head = self._dqueue[0]
                rk = head.resume_kv  # packed (payload, scale) or fp32
                kv_pos = int((rk[0] if isinstance(rk, tuple)
                              else rk).shape[3])
                need_t = max(
                    int(head.prompt.shape[1]) + head.n_new, kv_pos)
                if self._slab is None:
                    self._build_slab(need_t)
                elif need_t > self._slab_dims()[1]:
                    self._grow_slab(need_t)
                if not self._slab_free:
                    break
                sess = self._dqueue.popleft()
                slot = self._slab_free.pop(0)
            t_pop = time.perf_counter()
            if self._decode_import(sess, slot, dst):
                # a failed import goes back to the queue and is
                # counted when a prefill cohort takes it
                trace_mod.record_span("decode_queue_wait",
                                      sess.t_enqueue, t_pop,
                                      trace=sess.trace)
                any_in = True
        return any_in

    def _decode_import(self, sess: "_DecodeSession", slot: int,
                       dst) -> bool:
        """Transplant a migrated session's KV rows into slab row
        `slot` and join the fused batch directly. Any import failure
        (geometry drift across replicas, a torn checkpoint) demotes
        the session to ledger REPLAY instead of failing it —
        correctness never depends on the fast path."""
        t0 = time.perf_counter()
        kv = sess.resume_kv
        try:
            self._slab = self.model.import_slab_rows(
                self._slab, slot, kv)
        except BaseException as e:  # noqa: BLE001 — demote to replay
            sess.resume_kv = None
            self._release_slot(slot)
            self._fail_live_if_slab_lost(dst, "import", e)
            with self._decode_lock:
                self._dqueue.appendleft(sess)
            return False
        now = time.perf_counter()
        sess.resume_kv = None
        P = int(sess.prompt.shape[1])
        k0 = len(sess.toks)
        sess.slot = slot
        sess.pos = P + k0 - 1
        sess.left = sess.n_new - k0
        sess.tok = sess.toks[-1]
        sess.reply.state = "dispatching"
        sess.t_last_tok = now
        trace_mod.record_span("resume_import", t0, now,
                              trace=sess.trace, prompt=P, ledger=k0)
        dst.joins += 1
        with self._decode_lock:
            self._decode_live[slot] = sess
            dst.slots_in_use = len(self._decode_live)
        return True

    def _release_slot(self, slot: int) -> None:
        """Return a slab row to the free pool (sorted, so admission
        order stays deterministic)."""
        with self._decode_lock:
            self._slab_free.append(slot)
            self._slab_free.sort()

    def _decode_prefill(self, cohort, geom, dst) -> None:
        """Admit a cohort of `(sess, slot, ordinal)` in ONE fused
        prefill+scatter dispatch: every prompt is padded to the
        cohort's widest pow2 bucket, run through `prefill_slab` (which
        materialises the narrow cache in-graph, reads each row's real
        last-token logits, and scatters every layer's rows into the
        pooled slab), then each session samples its first token at
        generate()'s exact key-split position and streams it — the
        TTFT edge. Param streaming is paid once per cohort, not once
        per session. Chaos `prefill_fail` is checked per session
        BEFORE the dispatch, so a poisoned prompt fails ITS session
        and the rest of the cohort still admits; a failure of the
        fused dispatch itself fails the whole cohort (the batch shares
        one program) but never the sessions already streaming."""
        import jax

        model = self.model
        params = geom[0]
        inj = self.fault_injector
        pol = self.policy
        with trace_mod.span("decode.prefill.assemble"):
            members = []
            for sess, slot, ordinal in cohort:
                if inj is not None and inj.should("prefill_fail", ordinal):
                    self._release_slot(slot)
                    sess.slot = -1
                    self._decode_fail_session(sess, dst,
                                              ServeDispatchError(
                        f"decode prefill failed: injected prefill "
                        f"failure (session {ordinal})"))
                    continue
                members.append((sess, slot))
            if not members:
                return
            # one bucket for the cohort: the widest member's pow2 rung.
            # Prefilling a short prompt at a wider rung is exact — pad
            # rows write K/V the causal mask hides and decode overwrites
            # slot p before any query attends it (see prefill_slab).
            Pb = 1
            for sess, _ in members:
                P = self._prefill_len(sess)
                Pb = max(Pb, (pol.bucket_seq(P)
                              if pol.max_seq is not None and P <= pol.max_seq
                              else _pow2_ceil(P)))
            # bucket the cohort's batch dim on the pow2 ladder too — a
            # cohort of every size 1..prefill_batch would otherwise compile
            # its own executable (program-cache churn on every admission
            # mix). Pad rows carry an OUT-OF-BOUNDS slot index: XLA scatter
            # drops OOB updates, so a pad row touches nothing.
            Bp = len(members)
            Bb = (pol.bucket_batch(Bp) if Bp <= pol.max_batch
                  else _pow2_ceil(Bp))
            n_slots = self._slab_dims()[0]
            ids = np.zeros((Bb, Pb), np.int32)
            nvec = np.ones(Bb, np.int32)
            slotv = np.full(Bb, n_slots, np.int32)  # OOB => dropped
            for r, (sess, slot) in enumerate(members):
                # a ledger-REPLAY resume prefills prompt + toks[:-1]: the
                # rebuilt cache is bit-identical to the one the original
                # replica held when it produced toks[-1]
                row = sess.prompt[0]
                if sess.resumed and len(sess.toks) > 1:
                    row = np.concatenate(
                        [row, np.asarray(sess.toks[:-1], np.int32)])
                ids[r, :len(row)] = row
                nvec[r] = len(row)
                slotv[r] = slot
        t0 = time.perf_counter()
        put = self._slab_put
        try:
            with trace_mod.span("decode.prefill.dispatch"):
                logits, new_slab = model.prefill_slab(
                    params, self._slab, put(ids), put(nvec),
                    put(slotv))
            with trace_mod.span("decode.prefill.readback"):
                lg = np.asarray(logits)
        except BaseException as e:  # noqa: BLE001 — isolate: a failed
            # cohort dispatch fails ITS members, never the sessions
            # already streaming from the slab (unless it took the
            # slab they stream from with it)
            self._fail_live_if_slab_lost(dst, "prefill", e)
            for sess, slot in members:
                self._release_slot(slot)
                sess.slot = -1
                self._decode_fail_session(sess, dst,
                                          ServeDispatchError(
                    f"decode prefill failed: {e!r}"))
            return
        self._slab = new_slab
        now = time.perf_counter()
        trace_mod.record_span("prefill", t0, now, rows=Bp, bucket=Pb)
        with trace_mod.span("decode.prefill.scatter"):
            for r, (sess, slot) in enumerate(members):
                P = int(sess.prompt.shape[1])
                if sess.resumed:
                    # replay resume: the prefill rebuilt the KV state; the
                    # ledger already holds every produced token (streamed
                    # at admission) and toks[-1] is the next step's input
                    # — discard this row's logits, restore position state
                    k0 = len(sess.toks)
                    sess.slot = slot
                    sess.pos = P + k0 - 1
                    sess.left = sess.n_new - k0
                    sess.tok = sess.toks[-1]
                    sess.reply.state = "dispatching"
                    sess.t_last_tok = now
                    trace_mod.record_span("resume_replay", t0, now,
                                          trace=sess.trace, prompt=P,
                                          ledger=k0)
                    dst.prefills += 1
                    dst.joins += 1
                    with self._decode_lock:
                        self._decode_live[slot] = sess
                        dst.slots_in_use = len(self._decode_live)
                    continue
                if sess.temperature == 0.0:
                    # host argmax on identical float bits == the traced
                    # jnp.argmax (both first-max-wins): no extra dispatch
                    tok = int(np.argmax(lg[r]))
                else:
                    sess.key = jax.random.PRNGKey(sess.seed)
                    sess.key, sub = jax.random.split(sess.key)
                    sampler = model.sample_fn(sess.temperature,
                                              sess.top_k)
                    tok = int(np.asarray(
                        sampler(put(lg[r:r + 1]), sub))[0])
                sess.slot = slot
                sess.tok = tok
                sess.pos = P
                sess.left = sess.n_new - 1
                sess.toks.append(tok)
                sess.reply.state = "dispatching"
                sess.reply._push_token(tok)
                sess.t_last_tok = now
                trace_mod.record_span("ttft", sess.reply.t_submit, now,
                                      trace=sess.trace, prompt=P)
                slo_mod.observe("ttft", now - sess.reply.t_submit)
                dst.prefills += 1
                dst.joins += 1
                dst.tokens_streamed += 1
                if sess.left == 0:
                    self._decode_finish(sess, dst)
                else:
                    with self._decode_lock:
                        self._decode_live[slot] = sess
                        dst.slots_in_use = len(self._decode_live)

    def _decode_run_ahead(self, live, ahead: int = 0) -> int:
        """How many fused steps may dispatch as ONE scanned block
        (`decode_scan`) without delaying a join, leave, expiry, or
        sampled token: capped by `decode_block` and every session's
        remaining budget (less the `ahead` steps of a block in flight
        whose tokens the host has not handed out yet), collapsed to 1
        whenever a session samples (host-side key splits), carries a
        deadline (expiry is checked between dispatches), or queued work
        could take a free slot. The result is floored to a power of two
        so `decode_scan` compiles one program per LADDER RUNG, not one
        per distinct remaining-token count (the same churn-bounding
        argument as the prefill's shape buckets)."""
        k = self.decode_block
        for _, sess in live:
            if sess.left - ahead < k:
                k = sess.left - ahead
            if sess.temperature != 0.0 or sess.deadline is not None:
                return 1
        if k > 1:
            with self._decode_lock:
                if self._dqueue and self._slab_free:
                    return 1  # admission pending: stay token-granular
        if k < 1:
            return 1
        if k == self.decode_block:
            return k  # the configured block is its own ladder rung
        return 1 << (int(k).bit_length() - 1)

    def _decode_chains(self, live, ahead: int) -> bool:
        """Whether a block may follow, before the host reads it back,
        one that takes every live session `ahead` steps on: nobody
        leaves at its end, everybody is greedy (a sampled step's
        logits come to the host) with no deadline (expiry is checked
        between dispatches), and no slot is free. A session queues only
        while a slot is free (`submit_decode` sheds once every slot is
        reserved), so nothing can join before the block after it; the
        next join waits for a leave, which the host sees coming."""
        for _, sess in live:
            if (sess.left <= ahead or sess.temperature != 0.0
                    or sess.deadline is not None):
                return False
        with self._decode_lock:
            return not self._slab_free

    def _decode_fault_due(self) -> bool:
        """Whether the fault injector holds a failure or a hang for the
        next dispatch: that one is not chained, so it meets the
        unchained dispatch's retry."""
        inj, idx = self.fault_injector, self._decode_step_idx + 1
        return inj is not None and (inj.should("decode_fail", idx)
                                    or inj.should("decode_hang", idx))

    def _decode_fused_step(self, live, geom, dst) -> None:
        """Advance every live slot by ONE warm dispatch — a single
        step, or a `decode_scan` block of up to `decode_block` steps
        when `_decode_run_ahead` proves nothing joins/leaves inside it —
        with the forward tier's retry/backoff discipline; and while
        `_decode_chains` proves the live set cannot change at a block's
        end, dispatch the next block behind it (on the slab it returns,
        from the token row its steps end on, left on the device, at
        positions `pos + k`) before reading it back, so the device runs
        block n + 1 while the host reads back and hands out block n.
        While every live session is greedy the token is chosen in the
        program that computed the logits (`decode_scan`, k = 1 for a
        single step) and [k, Sb] int32 comes back; only a step with a
        sampled session among the live ones is `decode_step`, whose
        logits [Sb, V] cross to the host for `sample_fn` and its
        host-side key splits (the greedy rows beside it take the host's
        argmax), and nothing follows it unread. Tokens are streamed
        only AFTER their own block's readback, block after block, and
        only from its output — a retried dispatch recomputes from the
        UNCHANGED slab, so a delivered stream is never torn or
        duplicated. Returns with no block in flight, so the slab and
        every session's ledger agree."""
        params = geom[0]
        with trace_mod.span("decode.step.assemble"):
            Sb = self._slab_dims()[0]
            tokv = np.zeros(Sb, np.int32)
            posv = np.zeros(Sb, np.int32)
            sampled = False
            for slot, sess in live:
                tokv[slot] = sess.tok
                posv[slot] = sess.pos
                sampled = sampled or sess.temperature != 0.0
            k = self._decode_run_ahead(live)
            chain = self._decode_chains(live, k)
        blk = self._decode_dispatch(live, params, tokv, posv, k, sampled,
                                    dst)
        behind = None       # when the block before `blk` was read back
        while blk is not None:
            nxt = err = None
            if chain and self._decode_running and not self._decode_fault_due():
                with trace_mod.span("decode.step.assemble"):
                    k = self._decode_run_ahead(live, blk.k)
                    chain = self._decode_chains(live, blk.k + k)
                self._decode_step_idx += 1
                try:
                    nxt = self._decode_enqueue(params, blk.tok,
                                               blk.pos + blk.k, k, False,
                                               time.perf_counter())
                except Exception as e:  # `blk` is handed out first
                    err = e
            behind = self._decode_deliver(blk, live, dst, behind)
            if behind is None:
                return  # its sessions failed, and what was behind it
            if err is not None:
                # the next dispatch is an unchained one with its retry;
                # a failure that took the slab took the sessions' state
                self._fail_live_if_slab_lost(dst, "chained decode step",
                                             err)
            blk = nxt

    def _decode_dispatch(self, live, params, tokv, posv, k, sampled,
                         dst) -> Optional[_DecodeBlock]:
        """The unchained dispatch of one step or block, retried with
        backoff while the slab is untouched. Returns it in flight, or
        None once its sessions have failed (retries exhausted, or the
        donated slab gone with a failed attempt)."""
        from . import resilience

        inj = self.fault_injector
        t0 = time.perf_counter()
        attempt = 0
        while True:
            self._decode_step_idx += 1
            idx = self._decode_step_idx
            try:
                if inj is not None and inj.should("decode_hang", idx):
                    time.sleep(inj.hang_s)
                if inj is not None and inj.should("decode_fail", idx):
                    raise RuntimeError(
                        f"injected decode step failure (step {idx})")
                return self._decode_enqueue(params, tokv, posv, k,
                                            sampled, t0)
            except BaseException as e:  # noqa: BLE001 — retry below
                if attempt >= self.max_retries or self._slab_lost():
                    # retries exhausted (or nothing left to retry
                    # from): the fused step is the only way forward
                    # for these sessions — fail them loudly, free
                    # every slot for queued work
                    self._decode_fail_live(live, dst, (
                        f"fused decode step failed after {attempt} "
                        f"retries: {e!r}"))
                    return None
                attempt += 1
                time.sleep(resilience.backoff_delay_s(
                    attempt, self.backoff_s,
                    jitter=self.backoff_jitter,
                    seed=self._jitter_seed))

    def _decode_enqueue(self, params, tok, pos, k, sampled,
                        t0) -> _DecodeBlock:
        """Enqueue ONE step or block on the slab, its inputs put beside
        it (`tok` a host vector, or the device row a block in flight
        ends on). The slab becomes the one the program returns: the one
        it took is donated. Returns the block in flight, with the
        counters the model kept for it."""
        model, put = self.model, self._slab_put
        with trace_mod.span("decode.step.dispatch", steps=k):
            if sampled:     # k == 1: its keys split on the host
                out, self._slab = model.decode_step(
                    params, self._slab, put(tok), put(pos))
            else:
                out, self._slab = model.decode_scan(
                    params, self._slab, put(tok), put(pos), k)
        return _DecodeBlock(
            self._decode_step_idx, k, sampled, pos, out,
            None if sampled else model.take_next_tokens(),
            model.detach_step_counters(), t0)

    def _decode_fail_live(self, live, dst, msg: str,
                          lost: bool = False) -> None:
        """Fail a dispatch's sessions loudly and free their slots; give
        queued work a slab again where a failed program took it (or
        `lost`: a block that failed after its dispatch wrote it)."""
        self._rebuild_lost_slab(lost)
        for _, sess in live:
            self._decode_fail_session(sess, dst, ServeDispatchError(msg))
        with self._decode_lock:
            dst.slots_in_use = len(self._decode_live)

    def _decode_deliver(self, blk: _DecodeBlock, live, dst,
                        behind: Optional[float]) -> Optional[float]:
        """Read a block back with its own counters and hand its tokens
        to their sessions. Its `decode_step` record starts at its
        dispatch or, for a block dispatched `behind` another, where
        that one's readback ended: the time the block alone held the
        host. Returns when its readback ended, or None once its
        sessions have failed: the program had taken the slab, so there
        is nothing to retry from, and a block behind it read what it
        never wrote."""
        import jax

        model = self.model
        put = self._slab_put
        try:
            with trace_mod.span("decode.step.readback", steps=blk.k):
                out = np.asarray(blk.out)  # completes the dispatch
                counted = model.take_step_counters(blk.counters)
        except Exception as e:  # the program took the slab: no retry
            self._decode_fail_live(live, dst, (
                f"fused decode step failed: {e!r}"), lost=True)
            return None
        t_read = time.perf_counter()
        k = blk.k
        t0 = blk.t0 if behind is None else max(blk.t0, behind)
        block_s = t_read - t0
        step_s = block_s / k
        self._ema_decode_step_s = (
            step_s if not self._ema_decode_step_s
            else 0.8 * self._ema_decode_step_s + 0.2 * step_s)
        rate = (len(live) * k / block_s) if block_s > 0 else 0.0
        self._decode_tokens_ema = (
            rate if not self._decode_tokens_ema
            else 0.8 * self._decode_tokens_ema + 0.2 * rate)
        dst.decode_steps += k
        if not blk.sampled:
            dst.decode_steps_tokens += k
        if behind is not None:
            dst.decode_steps_chained += k
        for name, n in counted.items():
            dst.step_counters[name] += n
        Sb = self._slab_dims()[0]
        trace_mod.record_span("decode_step", t0, t_read,
                              rows=len(live), slots=Sb, steps=k)
        # tokens [k, Sb], chosen where the logits were computed; the
        # logits [Sb, V] of a single step only when a live session
        # samples
        lg, toks = (out, None) if blk.sampled else (None, out)
        with trace_mod.span("decode.step.scatter"):
            now = time.perf_counter()
            for slot, sess in live:
                if toks is not None:
                    seq = [int(t) for t in toks[:, slot]]
                elif sess.temperature == 0.0:
                    seq = [int(np.argmax(lg[slot]))]
                else:
                    sess.key, sub = jax.random.split(sess.key)
                    sampler = model.sample_fn(sess.temperature,
                                              sess.top_k)
                    seq = [int(np.asarray(
                        sampler(put(lg[slot:slot + 1]), sub))[0])]
                for tok in seq:
                    sess.toks.append(tok)
                    sess.reply._push_token(tok)
                    trace_mod.record_span("tpot", sess.t_last_tok, now,
                                          trace=sess.trace)
                    slo_mod.observe("tpot", now - sess.t_last_tok)
                    sess.t_last_tok = now
                    dst.tokens_streamed += 1
                sess.tok = seq[-1]
                sess.pos += k
                sess.left -= k
                if sess.left == 0:
                    self._decode_finish(sess, dst)
            with self._decode_lock:
                nlive = len(self._decode_live)
                qdepth = len(self._dqueue)
                dst.slots_in_use = nlive
            if self.metrics is not None:
                try:
                    extra = ({"quant": self._decode_quant}
                             if self._decode_quant != "off" else {})
                    self.metrics.log_step(
                        blk.idx,
                        examples=len(live) * k,
                        step_s=block_s, tier="decode",
                        sessions=len(live), slots=Sb, block=k,
                        slab_seq=self._slab_dims()[1],
                        occupancy=round(len(live) / Sb, 4),
                        queue_depth=qdepth,
                        tokens_streamed=dst.tokens_streamed,
                        completed=dst.completed, expired=dst.expired,
                        shed=dst.shed, failed=dst.failed, **extra)
                except Exception:
                    _STATS.errors += 1  # metrics stream closed mid-serve
        return t_read

    # -- dispatcher -------------------------------------------------------
    def _fail_request(self, req: _Request, err: BaseException,
                      expired: bool = False) -> bool:
        """Terminal failure accounting: every failed future bumps the
        legacy `errors` counter plus exactly one of
        `expired`/`failed` — the reconciliation invariant. Counts only
        when this write actually resolves the future (first write
        wins), so a request can never land in two terminal buckets;
        returns whether it did."""
        if not req.reply._fail(err):
            return False
        _STATS.errors += 1
        if expired:
            _STATS.expired += 1
        else:
            _STATS.failed += 1
        return True

    def _take_inflight(self) -> List[_Request]:
        with self._lock:
            taken = [r for r in self._inflight if not r.reply.done()]
            self._inflight = []
        return taken

    def _pop(self) -> Optional[_Request]:
        """Pop the oldest LIVE request: queued requests whose deadline
        already passed are expired here — before batch assembly, so a
        dispatch is never padded with rows nobody is waiting for."""
        while True:
            with self._lock:
                if not self._queue:
                    self._have_work.clear()
                    return None
                req = self._queue.popleft()
                self._depth = len(self._queue)
                _STATS.queue_depth = self._depth
            if (req.deadline is not None
                    and time.perf_counter() >= req.deadline):
                self._fail_request(req, ServeDeadlineError(
                    f"request expired in queue after "
                    f"{(time.perf_counter() - req.t_enqueue) * 1e3:.1f}"
                    " ms (deadline passed before batch assembly)"),
                    expired=True)
                continue
            return req

    def _effective_wait_s(self) -> float:
        """The coalesce window for this cycle. Adaptive mode shrinks
        it toward 0 as the smoothed queue depth approaches the shed
        watermark (or max_queue when none is set): under sustained
        backlog the engine stops paying latency for occupancy —
        latency degrades gracefully before availability does."""
        if not self.adaptive_wait:
            return self.max_wait_s
        wm = float(self.shed_watermark or self.max_queue)
        self._depth_ema = (0.8 * self._depth_ema
                           + 0.2 * self._depth)
        wait = self.max_wait_s * max(0.0, 1.0 - self._depth_ema / wm)
        _STATS.effective_wait_ms = round(wait * 1e3, 4)
        return wait

    def _supervised_loop(self) -> None:
        """The dispatcher thread target: `_loop` under a supervisor.
        An exception escaping the loop (a dispatcher bug, an injected
        `dispatcher_kill`) fails the in-flight futures LOUDLY and
        restarts the loop — bounded by `max_restarts`, after which the
        engine stops admitting and fails the remaining queue instead
        of flapping forever."""
        while True:
            try:
                self._loop()
                return  # clean exit (stop())
            except BaseException as e:  # noqa: BLE001 — supervisor
                for req in self._take_inflight():
                    self._fail_request(req, ServeDispatchError(
                        f"dispatcher died mid-dispatch: {e!r}"))
                _STATS.restarts += 1
                self._restarts += 1
                self._note_health(
                    "unhealthy", f"dispatcher died: {e!r}")
                if not self._running:
                    return
                if self._restarts > self.max_restarts:
                    with self._lock:
                        self._running = False
                        victims = list(self._queue)
                        self._queue.clear()
                        self._depth = 0
                        _STATS.queue_depth = 0
                    for req in victims:
                        self._fail_request(req, ServeClosedError(
                            f"dispatcher restarts exhausted "
                            f"({self.max_restarts}); engine stopped"))
                    self._note_health(
                        "unhealthy",
                        f"dispatcher restarts exhausted after {e!r}")
                    return
                # else: fall through — the while loop IS the restart

    def _loop(self) -> None:
        while True:
            req = self._pop()
            if req is None:
                if not self._running:
                    return
                self._have_work.wait(0.05)
                continue
            # Coalesce window: from the FIRST request of this batch,
            # wait up to the (possibly adaptively shrunk) window for
            # more work, stopping early when the batch is full. A
            # request that does not fit (wrong signature, or it would
            # overflow max_batch) is requeued at the FRONT below —
            # never reordered behind later requests of its own
            # signature. The scan stops once a full cycle's worth of
            # mismatches piled up: under deep alternating-signature
            # queues an unbounded scan would churn the whole deque
            # every dispatch.
            self._cycle_idx += 1
            group = [req]
            with self._lock:
                self._inflight = group
            req.reply.state = "dispatching"
            rows = req.n
            deadline = req.t_enqueue + self._effective_wait_s()
            pending: List[_Request] = []
            while rows < self.max_batch:
                nxt = self._pop()
                if nxt is None:
                    now = time.perf_counter()
                    if now >= deadline or not self._running:
                        break
                    self._have_work.wait(min(deadline - now, 0.005))
                    continue
                if nxt.sig != req.sig or rows + nxt.n > self.max_batch:
                    pending.append(nxt)
                    # a full batch is full regardless of signature;
                    # mixed-signature traffic dispatches next cycle
                    if (rows + nxt.n > self.max_batch
                            or len(pending) >= self.max_batch):
                        break
                    continue
                group.append(nxt)
                nxt.reply.state = "dispatching"
                rows += nxt.n
            # requeue the leftovers at the FRONT, preserving order
            if pending:
                with self._lock:
                    for p in reversed(pending):
                        self._queue.appendleft(p)
                    self._depth = len(self._queue)
                    _STATS.queue_depth = self._depth
                self._have_work.set()
            inj = self.fault_injector
            if inj is not None and inj.should("dispatcher_kill",
                                              self._cycle_idx):
                raise RuntimeError(
                    f"injected dispatcher kill (cycle "
                    f"{self._cycle_idx})")
            # Cleared only on successful return: if _dispatch escapes
            # with an exception, the supervisor must still find the
            # group in _inflight to fail its futures loudly — a
            # `finally` here would wipe it first and leave the
            # callers hanging until their own result() timeouts.
            # (_take_inflight skips futures _dispatch already
            # resolved, so nothing is double-failed.)
            self._dispatch(group, rows)
            with self._lock:
                self._inflight = []

    def _dispatch(self, group: List[_Request], rows: int) -> None:
        """One coalesced group: expire stale members, then dispatch
        with retry/backoff and poison bisection."""
        t_deq = time.perf_counter()
        live: List[_Request] = []
        for r in group:
            if r.deadline is not None and t_deq >= r.deadline:
                # Expired between pop and assembly: same pre-assembly
                # guarantee as the queue-side expiry in _pop.
                self._fail_request(r, ServeDeadlineError(
                    "request expired before batch assembly"),
                    expired=True)
                continue
            live.append(r)
            trace_mod.record_span("queue_wait", r.t_enqueue, t_deq,
                                  trace=r.trace, rows=r.n)
            # ISSUE 20: the online sketch sees EXACTLY the samples
            # the trace span records — bench cross-validates the two
            slo_mod.observe("queue_wait", t_deq - r.t_enqueue)
        if not live:
            return
        with self._lock:
            self._inflight = live
        rows = sum(r.n for r in live)
        err = self._dispatch_with_retry(live, rows)
        if err is None:
            self._consec_failures = 0
            self._update_health()
            return
        # Retries exhausted on the whole group: bisect to isolate the
        # poison request(s) — fail only what fails ALONE, re-dispatch
        # and deliver the rest. One bad input can't take out a
        # coalesced batch of 64.
        self._bisect(live, err)
        self._consec_failures += 1
        self._update_health()

    def _dispatch_with_retry(self, group: List[_Request],
                             rows: int) -> Optional[BaseException]:
        """Try the fused dispatch up to 1 + max_retries times with
        exponential backoff + seed-keyed jitter. Returns None on
        success, the final exception on exhaustion."""
        from . import resilience

        attempt = 0
        while True:
            try:
                self._dispatch_once(group, rows)
                return None
            except BaseException as e:  # noqa: BLE001 — isolate below
                _STATS.dispatch_failures += 1
                if attempt >= self.max_retries:
                    return e
                attempt += 1
                _STATS.retries += 1
                delay = resilience.backoff_delay_s(
                    attempt, self.backoff_s,
                    jitter=self.backoff_jitter,
                    seed=self._jitter_seed)
                t0 = time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                trace_mod.record_span(
                    "dispatch_retry", t0, time.perf_counter(),
                    attempt=attempt, error=repr(e))

    def _bisect(self, group: List[_Request], err: BaseException
                ) -> None:
        """Poison isolation: split the failed group and give each half
        ONE attempt (transient faults already had their retries);
        halves that still fail recurse down to single requests, which
        fail their own futures (counted `poisoned`). Everything else
        re-dispatches and delivers."""
        if len(group) == 1:
            r = group[0]
            # `poisoned` tracks a subset of `failed`: bump it only
            # when this fail actually resolves the future (the stop()
            # drain-timeout path may have beaten us to it).
            if self._fail_request(r, ServePoisonedError(
                    f"request failed dispatch alone after group "
                    f"bisection (poison input): {err!r}")):
                _STATS.poisoned += 1
            return
        mid = len(group) // 2
        for half in (group[:mid], group[mid:]):
            try:
                self._dispatch_once(half, sum(r.n for r in half))
            except BaseException as e:  # noqa: BLE001
                _STATS.dispatch_failures += 1
                self._bisect(half, e)

    def _chaos_attempt(self, group: List[_Request]) -> None:
        """Test-only fault hook on the dispatch path (the serving
        chaos harness). No-op without an injector. Poison requests
        fail DETERMINISTICALLY on every attempt (the bisection
        target); the transient kinds are keyed by the global attempt
        index, so a retry redraws."""
        inj = self.fault_injector
        if inj is None:
            return
        for r in group:
            if r.poison:
                raise ServeDispatchError(
                    "injected poison request: this input fails every "
                    "dispatch it rides in")
        idx = self._attempt_idx
        if inj.should("dispatch_hang", idx):
            time.sleep(inj.hang_s)
        if inj.should("dispatch_fail", idx):
            raise RuntimeError(
                f"injected transient dispatch failure (attempt {idx})")
        if inj.should("device_lost_serve", idx):
            from .resilience import DeviceLostError

            raise DeviceLostError(
                f"injected serving device loss (attempt {idx})")

    def _dispatch_once(self, group: List[_Request], rows: int) -> None:
        """One dispatch ATTEMPT: assemble, execute, scatter. Raises on
        failure (the retry/bisect layers above decide what happens
        next); on success the replies are delivered before this
        returns, and post-reply bookkeeping can't kill the thread."""
        from . import tensor as tensor_mod

        self._attempt_idx += 1
        self._chaos_attempt(group)
        t_dispatch0 = time.perf_counter()
        # The dispatch-level spans inherit the FIRST traced member's
        # context (a coalesced group can carry many trace ids — the
        # rest are listed on the batch_assemble span so no request's
        # timeline loses the dispatch it rode in).
        traced = [r.trace for r in group if r.trace]
        tids = sorted({t[0] for t in traced})
        targs = {"traces": tids} if len(tids) > 1 else {}
        with trace_mod.context(*(traced[0] if traced else (None,))):
            with trace_mod.span("batch_assemble", requests=len(group),
                                rows=rows, **targs):
                if len(group) == 1:
                    batch = list(group[0].arrays)
                else:
                    batch = [np.concatenate([g.arrays[i]
                                             for g in group])
                             for i in range(len(group[0].arrays))]
                padded, info = export_cache.pad_batch_to_bucket(
                    batch, self.policy)
                n_bucket = info["n_bucket"]
                dev = self._device()
                tensors = [tensor_mod.from_numpy(
                    np.ascontiguousarray(a), device=dev)
                    for a in padded]
            t0 = time.perf_counter()
            with trace_mod.span("dispatch", bucket=n_bucket) as sp_d:
                out = self.model._ensure_forward_exec()(*tensors)
            t_r0 = time.perf_counter()
            with trace_mod.span("reply", requests=len(group)) as sp_r:
                host = self._to_host(out, info)
                delivered = self._scatter(group, host, rows)
        if slo_mod.enabled():
            # ISSUE 20: the sketch sees the IDENTICAL durations the
            # spans recorded (the bench cross-validates the two —
            # separate clock reads diverge by tens of µs under load,
            # which is >4% of a sub-ms reply segment); the local
            # reads are only the tracing-disabled fallback
            t_r1 = time.perf_counter()
            slo_mod.observe("dispatch",
                            getattr(sp_d, "dur_s", None) or t_r0 - t0)
            slo_mod.observe("reply",
                            getattr(sp_r, "dur_s", None) or t_r1 - t_r0)
        dispatch_s = time.perf_counter() - t0
        self._dispatch_idx += 1
        # Rolling dispatch time (attempt start -> replies out) feeds
        # the overload retry_after_ms estimate.
        whole_s = time.perf_counter() - t_dispatch0
        self._ema_dispatch_s = (whole_s if not self._ema_dispatch_s
                                else 0.8 * self._ema_dispatch_s
                                + 0.2 * whole_s)
        try:  # replies are out — bookkeeping must not kill the thread
            _STATS.note_dispatch(len(group), rows, n_bucket)
            _STATS.replies += delivered
            with self._lock:  # percentiles() reads from caller threads
                for r in group:
                    self._latencies.append(r.reply.latency_s)
            if self.metrics is not None:
                p = self.percentiles()
                self.metrics.log_step(
                    self._dispatch_idx, examples=rows,
                    step_s=dispatch_s,
                    requests=len(group), rows=rows, bucket=n_bucket,
                    occupancy=round(rows / n_bucket, 4),
                    pad_fraction=round((n_bucket - rows) / n_bucket, 4),
                    queue_depth=self._depth,
                    p50_ms=p["p50_ms"], p95_ms=p["p95_ms"],
                    p99_ms=p["p99_ms"],
                    expired=_STATS.expired, shed=_STATS.shed,
                    retries=_STATS.retries, failed=_STATS.failed)
        except Exception:
            _STATS.errors += 1  # e.g. metrics stream closed mid-serve

    def _device(self):
        ps = self.model.param_tensors()
        if ps:
            return ps[0].device
        from .device import get_default_device

        return get_default_device()

    @staticmethod
    def _to_host(out, info):
        """Flatten the reply pytree to host numpy and undo the bucket
        padding (`export_cache.slice_bucket_out`): pad ROWS come off
        every batch-carrying leaf, and when the policy bucketed a
        sequence dim the pad POSITIONS come off too — a reply must
        never carry fabricated repeated-final-position output."""
        import jax

        host = jax.tree_util.tree_map(
            lambda t: np.asarray(getattr(t, "data", t)), out,
            is_leaf=lambda t: hasattr(t, "data") or hasattr(t, "shape"))
        return export_cache.slice_bucket_out(host, info)

    def _scatter(self, group: List[_Request], host, rows: int) -> int:
        """Deliver per-request reply rows. Returns how many futures
        this dispatch actually resolved — a delivery racing a future
        the stop() drain-timeout path already failed loses (first
        write wins) and must not count as a reply."""
        import jax

        now = time.perf_counter()
        delivered = 0
        off = 0
        for r in group:
            lo, hi = off, off + r.n
            off = hi

            def cut(a, lo=lo, hi=hi):
                if (getattr(a, "ndim", 0) >= 1
                        and a.shape[0] == rows):
                    return a[lo:hi]
                return a  # non-batch leaf: shared across requests

            late = r.deadline is not None and now >= r.deadline
            if late:
                r.reply.deadline_exceeded = True
            if r.reply._deliver(jax.tree_util.tree_map(cut, host)):
                delivered += 1
                if late:
                    # Expired mid-dispatch: the work is done and the
                    # reply delivered — count it `late` so the caller
                    # knows the SLO was missed.
                    _STATS.late += 1
        return delivered

    # -- health -----------------------------------------------------------
    def _note_health(self, state: str, reason: str) -> None:
        """Force-record a health transition from an internal event
        (the supervisor catching a dead loop) — `health()` computed
        from live signals would miss it, because the supervisor IS the
        dispatcher thread and restarts immediately."""
        with self._health_lock:
            if state != self._health_state:
                self._health_state = state
                self.health_transitions.append((state, reason))
            self._write_health_file({"state": state,
                                     "reasons": [reason]})

    def _update_health(self) -> None:
        self.health()

    def health(self) -> Dict:
        """Liveness/readiness snapshot for fleet probes:
        `state` in {"ready", "degraded", "unhealthy"} plus the reasons
        and the load-bearing counters. `degraded` = still serving but
        under pressure (queue at/above the watermark, a dispatch
        failure streak below the unhealthy threshold); `unhealthy` =
        not serving (stopped, dispatcher dead/hung, restarts
        exhausted) or failing every dispatch. Calling it records a
        transition in `health_transitions` when the state changed and
        refreshes `health_file` (the `tools/serve_health.py` probe
        surface)."""
        reasons: List[str] = []
        thread = self._thread
        alive = thread is not None and thread.is_alive()
        if self._hung_at_stop:
            state = "unhealthy"
            reasons.append("dispatcher hung past the stop drain "
                           "timeout (thread abandoned)")
        elif not self._running:
            state = "unhealthy"
            reasons.append("engine not running")
        elif not alive:
            state = "unhealthy"
            reasons.append("dispatcher thread dead")
        elif self._consec_failures >= self.unhealthy_failures:
            state = "unhealthy"
            reasons.append(
                f"{self._consec_failures} consecutive dispatch "
                f"failures (threshold {self.unhealthy_failures})")
        else:
            state = "ready"
            if self._consec_failures > 0:
                state = "degraded"
                reasons.append(
                    f"{self._consec_failures} consecutive dispatch "
                    "failure(s)")
            wm = self.shed_watermark or self.max_queue
            if self._depth >= int(wm):
                state = "degraded"
                reasons.append(
                    f"queue depth {self._depth} at the shed "
                    f"watermark ({wm})")
        with self._decode_lock:
            decode_active = (len(self._decode_live)
                             + len(self._dqueue))
            decode_free = max(
                0, self.max_sessions - self._decode_reserved)
        snap = {
            "state": state,
            "reasons": reasons,
            "queue_depth": self._depth,
            "consecutive_failures": self._consec_failures,
            "restarts": self._restarts,
            "expired": _STATS.expired,
            "shed": _STATS.shed,
            "retries": _STATS.retries,
            "failed": _STATS.failed,
            # decode-tier saturation (ISSUE 17): rides every health
            # snapshot — and therefore every fleet heartbeat — so
            # admission-aware placement can see per-replica KV-slot
            # occupancy without extra wire traffic
            "decode": {
                "active_sessions": decode_active,
                "free_slots": decode_free,
                "tokens_per_s": round(self._decode_tokens_ema, 3),
                # quant mode (ISSUE 19) rides every heartbeat — the
                # fleet router can see a replica serving int8 without
                # extra wire traffic (MIGRATE targets must match)
                "quant": self._decode_quant,
            },
        }
        # ISSUE 20: alert counts ride health ONLY while the SLO
        # engine is armed — older snapshots (and every disabled run)
        # stay byte-identical
        counts = slo_mod.alert_counts()
        if counts is not None:
            snap["alerts"] = counts
        with self._health_lock:
            if state != self._health_state:
                self._health_state = state
                self.health_transitions.append(
                    (state, "; ".join(reasons) or "ok"))
                self._write_health_file(snap)
        return snap

    def _write_health_file(self, snap: Dict) -> None:
        if not self.health_file:
            return
        import json
        import os

        payload = dict(snap)
        payload["time"] = round(time.time(), 3)
        # Which process wrote this? A fleet of per-replica snapshots
        # from separate worker processes (ISSUE 13) is only debuggable
        # when each file names its writer.
        payload.setdefault("pid", os.getpid())
        tmp = f"{self.health_file}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(payload, f)
            os.replace(tmp, self.health_file)
        except OSError:
            _STATS.errors += 1  # health probe rot is loud in counters

    # -- SLO percentiles --------------------------------------------------
    def percentiles(self) -> Dict[str, Optional[float]]:
        """Rolling request-latency percentiles (ms) over the last
        `latency_window` replies — the SLO numbers the metrics stream
        and the bench report."""
        with self._lock:
            lat = [l for l in self._latencies if l is not None]
        if not lat:
            return {"p50_ms": None, "p95_ms": None, "p99_ms": None}
        arr = np.asarray(lat) * 1e3
        return {"p50_ms": round(float(np.percentile(arr, 50)), 3),
                "p95_ms": round(float(np.percentile(arr, 95)), 3),
                "p99_ms": round(float(np.percentile(arr, 99)), 3)}


# ---------------------------------------------------------------------------
# Retry-after-aware client submit (the documented ServeOverloadError
# contract, packaged): bench's serve/fleet load generators and any
# in-process client use this instead of treating a shed as terminal.
# ---------------------------------------------------------------------------
def submit_with_backoff(submit, *arrays, deadline_ms: Optional[float]
                        = None, max_attempts: int = 3, seed: int = 0,
                        max_sleep_s: float = 1.0):
    """Call `submit(*arrays, deadline_ms=...)` honoring the
    `ServeOverloadError.retry_after_ms` back-off contract: a shed is a
    structured "come back in N ms" hint, not a terminal failure, so
    the client sleeps the hinted delay — scaled by the deterministic
    seed-keyed jitter of `resilience.backoff_delay_s` (a fleet of
    clients sleeping the exact same hint would re-arrive in lockstep
    and shed again) and capped at `max_sleep_s` — then retries, up to
    `max_attempts` total attempts. The final attempt's
    `ServeOverloadError` propagates; every other error propagates
    immediately (a queue-full drop or overflow carries no retry
    hint). `submit` is any callable with the `ServingEngine.submit` /
    `FleetRouter.submit` signature; returns whatever it returns.

    Tracing (ISSUE 15): with the tracer on, ONE trace context spans
    every attempt — the request that finally lands carries the same
    `trace_id` its shed-and-retried earlier attempts did, and each
    hinted wait is a `shed_backoff` span on that timeline. Strict
    no-op while tracing is disabled."""
    from . import resilience

    ctx = trace_mod.current_trace()
    tid = (ctx["trace_id"] if ctx
           else (trace_mod.new_trace_id() if trace_mod.enabled()
                 else None))
    attempt = 0
    while True:
        attempt += 1
        try:
            with trace_mod.context(tid):
                return submit(*arrays, deadline_ms=deadline_ms)
        except ServeOverloadError as e:
            if attempt >= int(max_attempts):
                raise
            # backoff_delay_s doubles per attempt on top of the hint:
            # a queue still at the watermark after the first hinted
            # wait needs MORE room, not the same wait again.
            delay = resilience.backoff_delay_s(
                attempt, max(e.retry_after_ms, 1.0) / 1e3,
                jitter=0.5, seed=int(seed), salt="client-shed")
            t0 = time.perf_counter()
            time.sleep(min(delay, float(max_sleep_s)))
            trace_mod.record_span(
                "shed_backoff", t0, time.perf_counter(), trace=tid,
                attempt=attempt, retry_after_ms=e.retry_after_ms)


# ---------------------------------------------------------------------------
# Offline prewarm (tools/prewarm.py drives this)
# ---------------------------------------------------------------------------
def prewarm_forward(model, sample_spec, policy=None,
                    max_batch: Optional[int] = None,
                    dry_run: bool = False) -> List[Dict]:
    """Populate the AOT export cache with the EVAL forward executable
    for every bucket a serving config can dispatch, so a serving
    worker's cold start is deserialize-only. `sample_spec` is one
    (per_sample_shape, dtype) pair per model input — the batch dim is
    prepended per bucket. With `dry_run=True` nothing traces: each
    bucket's artifact key is computed (`_JitForward.export_key`) and
    reported present/missing. Returns one row per bucket:
    {bucket, seq, key, status} with status in
    {"present", "missing", "built"}.

    Requires an armed store (`device.set_export_cache`) — prewarming
    into a disabled cache would trace for nothing and warm no one.
    """
    from . import tensor as tensor_mod
    from .device import get_default_device

    if not export_cache.active():
        raise RuntimeError(
            "prewarm needs an armed export cache: call "
            "device.set_export_cache(dir) first")
    pol = (policy or export_cache.bucket_policy()
           or export_cache.BucketPolicy(
               max_batch=_pow2_ceil(max_batch
                                    or get_config()["max_batch"])))
    ceiling = (min(pol.max_batch, _pow2_ceil(max_batch))
               if max_batch else pol.max_batch)
    batches = []
    b = 1
    while b <= ceiling:
        batches.append(b)
        b <<= 1
    seqs: List[Optional[int]] = [None]
    if pol.seq_dim is not None:
        seqs = []
        s = 1
        while s <= pol.max_seq:
            seqs.append(s)
            s <<= 1
    was_training = model.training
    model.eval()
    # Inputs go to the MODEL's device: on a multi-device host (or the
    # 8-virtual-device CPU mesh) a model living off device 0 would
    # otherwise get default-device inputs and fail the jit with an
    # incompatible-devices error.
    ps = model.param_tensors()
    dev = ps[0].device if ps else get_default_device()
    rows: List[Dict] = []
    try:
        fwd = model._ensure_forward_exec()
        for b in batches:
            for s in seqs:
                tensors = []
                for shape, dtype in sample_spec:
                    shape = list(shape)
                    if s is not None and len(shape) >= pol.seq_dim:
                        shape[pol.seq_dim - 1] = s  # seq_dim counts
                        # the batch dim; per-sample shapes don't
                    arr = np.zeros([b] + shape, dtype=np.dtype(dtype))
                    tensors.append(tensor_mod.from_numpy(arr,
                                                         device=dev))
                key = fwd.export_key(*tensors)
                if export_cache.artifact_exists(key):
                    status = "present"
                elif dry_run:
                    status = "missing"
                else:
                    model.forward_graph(*tensors)  # trace + publish
                    status = ("built" if export_cache.artifact_exists(
                        key) else "missing")
                rows.append({"bucket": b, "seq": s, "key": key,
                             "status": status})
    finally:
        model.train(was_training)
    return rows
