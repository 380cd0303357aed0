"""Multi-process fleet transport (ISSUE 13; ROADMAP item 2(a), the
remaining leg): `ProcReplica` puts a real WORKER PROCESS behind the
PR 11 `Replica` protocol, so the `FleetRouter` fronts actual process
boundaries — a SIGKILL takes out one worker, not the fleet — without
touching a line of routing logic.

Topology: the parent spawns `python -m singa_tpu.fleet_worker` (one
per replica), which builds the SAME model from a deterministic
spec-named factory, arms the shared export-cache store, runs a
`ServingEngine`, and serves a length-prefixed CHECKSUMMED framed
protocol over a loopback socket. With the store prewarmed
(`tools/prewarm.py`, populate-once-start-N) a worker's cold start —
and every supervisor RESPAWN after a kill — is deserialize-only
(export hits >= 1, traces == 0), the PHAST portable-compiled-artifact
lesson (arxiv 2005.13076) doing the heavy lifting of the restart
story.

Robustness is the product, not a feature:

  framing      — every frame is `SF` magic + version + type + length
      + request id + a CRC32 over the payload. A torn or corrupted
      frame can NEVER be delivered as data: the reader declares the
      stream corrupt (`FrameCorruptError`), fails every in-flight
      future loudly, and kills the worker so the supervisor respawns
      it from the store — fail closed, bounded, counted
      (`torn_frames_detected`).
  IPC deadlines — every admitted request carries a transport deadline
      (`ipc_deadline_ms` + the caller's own deadline). A reply that
      does not arrive in time fails the caller's future with a
      structured `ProcTransportError` — a `ServeDispatchError`
      subclass, so the PR 11 failover path re-submits to a different
      replica unchanged. Admission itself is synchronous (REQ -> ACK),
      so submit-time refusals (shed, queue-full, overflow, closed)
      keep their exact single-engine types and the router's shed-aware
      retry fires as before.
  heartbeats   — the worker streams `HB` frames (engine `health()`
      snapshot + terminal counters + export counters) every
      `heartbeat_interval_s`. `ProcReplica.health()` returns the LAST
      heartbeat with the worker's own wall-clock stamp, so a wedged or
      dead worker's snapshot simply ages and the router's existing
      stale-snapshot ejection fires: missed heartbeat => stale =>
      fail-closed ejection, exactly the PR 11 path.
  crash detection — the reader thread sees EOF/exit, records the child
      exit code, fails every in-flight future (`ProcTransportError` =>
      failover), and flips `killed` so the router supervisor respawns
      the worker, bounded by `max_restarts`.
  backpressure — the parent bounds in-flight requests per worker
      (`max_inflight`); past it, submit sheds with a structured
      `ServeOverloadError.retry_after_ms` (the worker's own hint from
      its last heartbeat) instead of ballooning the pipe.
  reconciliation — the parent MIRRORS every IPC request into the
      process-local `cache_stats()["serve"]` terminal counters
      (exactly one terminal bucket per request), so the three PR 11
      `fleet.reconcile` equations hold across the process boundary
      unchanged; per-generation accounting (`admitted == frames +
      swept` at quiescence) plus the end-of-run handshake (the worker
      ships its final counters in the `BYE` frame; a SIGKILLed
      generation's in-flight requests are swept into `failed`) is
      checked by `fleet.reconcile_transport` — a killed-in-flight
      request lands in `failed`/failover, never vanishes.

Chaos: `resilience.FaultInjector` kinds `proc_sigkill` (a REAL
`os.kill(pid, SIGKILL)`), `proc_hang` (the worker's next dispatch
sleeps), `pipe_stall` (the parent's next frame write stalls), and
`torn_frame` (the worker corrupts its next reply frame) are keyed by
the router submit ordinal and consumed by `FleetRouter._chaos_route`.

Knobs: `device.set_fleet(transport=..., ipc_deadline_ms=...,
heartbeat_interval_s=..., spawn_timeout_s=..., max_inflight=...)`.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import export_cache
from . import slo as slo_mod
from . import trace as trace_mod
from .serve import (
    ServeClosedError,
    ServeDeadlineError,
    ServeDispatchError,
    ServeMigratedError,
    ServeOverloadError,
    ServePoisonedError,
    ServeQueueFullError,
    ServeReply,
    ServingEngine,
    note_remote_decode_export,
    note_remote_decode_session,
    note_remote_decode_terminal,
    note_remote_decode_tokens,
    note_remote_request,
    note_remote_terminal,
)

__all__ = [
    "ProcReplica",
    "ProcTransportError",
    "FrameCorruptError",
    "FrameReplayError",
    "FrameGapError",
    "encode_frame",
    "send_frame",
    "FrameReader",
    "encode_tree",
    "decode_tree",
    "decode_tree_prefix",
    "encode_req_payload",
    "decode_req_payload",
    "encode_decode_payload",
    "decode_decode_payload",
    "encode_resume_payload",
    "decode_resume_payload",
    "encode_trace_suffix",
    "decode_trace_suffix",
    "encode_error",
    "decode_error",
    "resolve_factory",
]


class ProcTransportError(ServeDispatchError):
    """The process boundary failed this request: the worker died with
    it in flight, the IPC deadline passed without a reply, or the
    frame stream went corrupt. Subclasses `ServeDispatchError` so the
    PR 11 `FleetRouter` failover path re-submits to a different
    replica unchanged — a transport failure is a fact about the
    replica, never about the input."""


class FrameCorruptError(RuntimeError):
    """A frame failed its structural checks (bad magic/version, an
    insane length, or a CRC32 mismatch): the stream cannot be trusted
    past this point. The reader fails in-flight futures loudly and
    the worker is killed/respawned — a truncated reply must never be
    delivered as data, and resyncing a corrupt byte stream would be a
    guess. On a TCP transport (ISSUE 18) the connection is torn down
    instead and the worker gets its bounded reconnect window — the
    STREAM is untrusted, not necessarily the process."""


class FrameReplayError(FrameCorruptError):
    """A frame arrived carrying a per-direction sequence number the
    receiver has ALREADY consumed: a middlebox duplicated it, or a
    stale connection replayed old bytes. Counted
    (`replay_frames_detected`) and treated as stream corruption —
    delivering it would double-deliver data, which the transport
    contract forbids."""


class FrameGapError(FrameCorruptError):
    """A frame arrived with a sequence number PAST the next expected
    ordinal: frames were reordered or silently dropped in transit
    (TCP itself never does this — a proxy, middlebox, or reconnect
    race did). Counted (`gap_frames_detected`) and treated as stream
    corruption: delivering out-of-order frames would reorder replies
    against their ACKs."""


# ---------------------------------------------------------------------------
# Wire format v2: 24-byte header + payload.
#   magic "SF" | version u8 | type u8 | payload_len u32 | req_id u64
#   | seq u32 | crc32(payload) u32
# `seq` is a per-connection, per-direction monotonic counter starting
# at 0 (ISSUE 18): a duplicated frame replays a seq the receiver has
# already consumed (`FrameReplayError`), a reordered or dropped frame
# leaves a gap (`FrameGapError`) — either way the stream is declared
# corrupt LOUDLY instead of delivering data twice or out of order. A
# reconnect is a fresh connection, so both directions restart at 0.
# ---------------------------------------------------------------------------
_MAGIC = b"SF"
_VERSION = 2
_HDR = struct.Struct(">2sBBIQII")
_MAX_PAYLOAD = 256 * 1024 * 1024  # structural sanity bound, not a knob
# Parent-side shipped-span buffer bound (per replica) + the per-frame
# piggyback bounds the worker drains into REP/HB/BYE frames. REPLY
# frames carry spans only under ship-buffer PRESSURE (>= half full):
# span bytes on the request path cost latency, so the steady-state
# carrier is the heartbeat and the reply piggyback is the relief
# valve that keeps drops bounded under bursts.
_MAX_SHIPPED = 8192
SPANS_PER_REP = 64
SPANS_PER_HB = 256
SPANS_PER_BYE = 2048

# Frame types.
HELLO = 1    # worker -> parent: {token, pid, name} (connection auth)
REQ = 2      # parent -> worker: deadline_ms f64 + encoded arrays
ACK = 3      # worker -> parent: request admitted (empty payload)
REP = 4      # worker -> parent: flags u8 (bit0 = late) + encoded tree
ERR = 5      # worker -> parent: JSON structured error (see encode_error)
HB = 6       # worker -> parent: JSON heartbeat (health+counters+export)
CTRL = 7     # parent -> worker: JSON {op, ...}
CTRL_OK = 8  # worker -> parent: JSON result for a sync CTRL/WARM
WARM = 9     # parent -> worker: encoded arrays (engine.warmup)
BYE = 10     # worker -> parent: JSON final counters (the reconciliation
             # handshake) — last frame of a clean drain/stop
# Decode-tier session frames (ISSUE 17). Byte-absent when unused: a
# fleet that never calls submit_decode puts none of these on the wire,
# and the forward-tier frame stream is byte-identical to PR 13.
DECODE = 11  # parent -> worker: decode-session params tree (+ optional
             # trace suffix) — ACKed synchronously like REQ
TOK = 12     # worker -> parent: one streamed token (i32) as its fused
             # decode step lands — feeds the parent reply's stream
MIGRATE = 13 # worker -> parent: the session's live-migration
             # checkpoint (drain path) — supersedes ERR: a migrated
             # session has no local terminal, it re-admits elsewhere
RESUME = 14  # parent -> worker: checkpoint admission (encoded ckpt
             # tree + optional trace suffix) — ACKed like DECODE
# TCP transport handshake frames (ISSUE 18). Spawn mode never puts
# these on the wire.
WELCOME = 15 # parent -> worker: JSON {fence, gen, spec?} — the
             # parent accepted this connection's HELLO; `fence` is the
             # generation-fence epoch the worker must echo on every
             # reconnect, `spec` ships only when the HELLO asked
             # (need_spec: a remotely launched worker has no env spec)
FENCED = 16  # parent -> worker: JSON {reason} — the connection's
             # HELLO carried a stale (or missing) fence: this worker
             # generation is superseded and must NOT serve; the parent
             # closes after sending. Counted stale_reconnects_refused.


def send_frame(sock, frame: bytes, deadline_s: float = 10.0) -> None:
    """Write one frame to `sock` COMPLETELY or fail — never leave a
    partial frame on the wire and return control (satellite: partial-
    write hardening). `sock.sendall` under a socket timeout can write
    a PREFIX of the frame and then raise `socket.timeout`; a retry of
    the next frame would interleave bytes mid-frame and corrupt the
    stream unrecoverably. This loop retries short writes on the SAME
    frame until `deadline_s` expires; on expiry (or any socket error
    mid-frame) it raises OSError — callers must treat the connection
    as broken, because bytes of a half-frame may already be out."""
    view = memoryview(frame)
    deadline = time.perf_counter() + deadline_s
    while view:
        try:
            sent = sock.send(view)
        except socket.timeout:
            if time.perf_counter() >= deadline:
                raise OSError(
                    f"send deadline ({deadline_s}s) expired with "
                    f"{len(view)}/{len(frame)} frame bytes unwritten: "
                    "connection is congested past tolerance") from None
            continue
        except InterruptedError:
            continue
        if sent == 0:
            raise OSError("socket connection broken mid-frame")
        view = view[sent:]


def encode_frame(ftype: int, req_id: int, payload: bytes,
                 corrupt: bool = False, seq: int = 0) -> bytes:
    """One wire frame. `corrupt=True` (the `torn_frame` chaos hook)
    flips payload bytes AFTER the CRC is computed — the receiver's
    checksum must catch it, which is the point. `seq` is the sender's
    per-connection monotonic ordinal for this direction."""
    crc = zlib.crc32(payload) & 0xFFFFFFFF
    if corrupt and payload:
        payload = bytes([payload[0] ^ 0xFF]) + payload[1:]
    elif corrupt:
        crc ^= 0xDEADBEEF
    return _HDR.pack(_MAGIC, _VERSION, ftype, len(payload),
                     req_id, seq & 0xFFFFFFFF, crc) + payload


# Amortized-compaction tuning for FrameReader: the consumed prefix is
# only sliced off once it dominates the buffer (and is big enough to
# matter), so a slow-drip byte stream costs O(total_bytes) instead of
# the old per-frame `del buf[:k]` O(n^2) re-copy.
_COMPACT_MIN = 1 << 16


class FrameReader:
    """Incremental frame parser over a byte stream. `feed(chunk)`
    returns every COMPLETE frame the buffer now holds; a partial
    frame waits for more bytes (a short read is normal, not an
    error), but structural damage — bad magic/version, a length past
    `max_frame_bytes`, a CRC mismatch — raises `FrameCorruptError`
    immediately. With `check_seq=True` (the live transport) every
    frame's header seq must be EXACTLY the next expected ordinal:
    a replayed/duplicated frame raises `FrameReplayError`, a gap
    (reorder or loss) raises `FrameGapError` — both subclass
    `FrameCorruptError`, so every existing fail-closed path applies.

    Parsing keeps a read cursor (`_off`) into one growing buffer and
    compacts the consumed prefix AMORTIZED (only once it exceeds both
    `_COMPACT_MIN` and half the buffer): under 1-byte slow-drip
    arrival the old per-frame front-slice was quadratic in stream
    length."""

    def __init__(self, max_frame_bytes: Optional[int] = None,
                 check_seq: bool = False):
        self._buf = bytearray()
        self._off = 0
        cap = _MAX_PAYLOAD if max_frame_bytes is None \
            else int(max_frame_bytes)
        self.max_frame_bytes = min(max(cap, 1), _MAX_PAYLOAD)
        self._check_seq = bool(check_seq)
        self._expect_seq = 0

    def feed(self, chunk: bytes) -> List[Tuple[int, int, bytes]]:
        self._buf.extend(chunk)
        out: List[Tuple[int, int, bytes]] = []
        buf = self._buf
        off = self._off
        try:
            while len(buf) - off >= _HDR.size:
                magic, ver, ftype, n, rid, seq, crc = _HDR.unpack_from(
                    buf, off)
                if magic != _MAGIC or ver != _VERSION:
                    raise FrameCorruptError(
                        f"bad frame header (magic {magic!r}, version "
                        f"{ver}): stream corrupt")
                if n > self.max_frame_bytes:
                    raise FrameCorruptError(
                        f"frame claims {n} payload bytes (cap "
                        f"{self.max_frame_bytes}): refusing to buffer "
                        "it — stream corrupt")
                if len(buf) - off < _HDR.size + n:
                    break  # torn so far — wait for the rest
                payload = bytes(buf[off + _HDR.size:
                                    off + _HDR.size + n])
                off += _HDR.size + n
                if (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
                    raise FrameCorruptError(
                        f"frame {rid} type {ftype} failed its CRC32: "
                        "a torn/corrupt reply must never be delivered "
                        "as data")
                if self._check_seq:
                    want = self._expect_seq & 0xFFFFFFFF
                    if seq != want:
                        if ((want - seq) & 0xFFFFFFFF) <= 0x7FFFFFFF:
                            raise FrameReplayError(
                                f"frame {rid} type {ftype} replays "
                                f"seq {seq} (expected {want}): a "
                                "duplicated frame must never be "
                                "delivered twice")
                        raise FrameGapError(
                            f"frame {rid} type {ftype} arrives at seq "
                            f"{seq} (expected {want}): frames were "
                            "reordered or lost in transit")
                    self._expect_seq += 1
                out.append((ftype, rid, payload))
        finally:
            self._off = off
            if off and (off == len(buf)
                        or (off > _COMPACT_MIN and off > len(buf) // 2)):
                del buf[:off]
                self._off = 0
        return out

    def pending_bytes(self) -> int:
        return len(self._buf) - self._off


# ---------------------------------------------------------------------------
# Payload codec: numpy pytrees (the serve request/reply shapes) without
# pickle — deterministic bytes, no code execution on decode.
# ---------------------------------------------------------------------------
_T_ARR, _T_LIST, _T_TUPLE, _T_DICT, _T_NONE = b"A", b"L", b"T", b"D", b"0"
_MAX_DEPTH = 16


def encode_tree(node) -> bytes:
    out: List[bytes] = []
    _enc(node, out, 0)
    return b"".join(out)


def _enc(node, out: List[bytes], depth: int) -> None:
    if depth > _MAX_DEPTH:
        raise ValueError("reply tree deeper than the wire codec's "
                         f"bound ({_MAX_DEPTH})")
    if node is None:
        out.append(_T_NONE)
        return
    if isinstance(node, (list, tuple)):
        out.append(_T_LIST if isinstance(node, list) else _T_TUPLE)
        out.append(struct.pack(">I", len(node)))
        for child in node:
            _enc(child, out, depth + 1)
        return
    if isinstance(node, dict):
        out.append(_T_DICT)
        out.append(struct.pack(">I", len(node)))
        for k in node:  # insertion order — round-trips exactly
            kb = str(k).encode("utf-8")
            out.append(struct.pack(">H", len(kb)))
            out.append(kb)
            _enc(node[k], out, depth + 1)
        return
    a = np.asarray(getattr(node, "data", node))
    # ascontiguousarray promotes 0-d to 1-d: reshape back
    a = np.ascontiguousarray(a).reshape(a.shape)
    dt = a.dtype.str.encode("ascii")
    out.append(_T_ARR)
    out.append(struct.pack(">B", len(dt)))
    out.append(dt)
    out.append(struct.pack(">B", a.ndim))
    out.append(struct.pack(f">{a.ndim}Q", *a.shape))
    raw = a.tobytes()
    out.append(struct.pack(">Q", len(raw)))
    out.append(raw)


def decode_tree(buf: bytes):
    node, off = _dec(buf, 0, 0)
    if off != len(buf):
        raise FrameCorruptError(
            f"payload has {len(buf) - off} trailing bytes after the "
            "tree: codec desync")
    return node


def decode_tree_prefix(buf: bytes, off: int = 0):
    """Decode one tree starting at `off`, returning (node, end_off) —
    for payloads that carry a structured suffix AFTER the tree (the
    optional trace block on REQ frames). Callers that expect nothing
    after the tree must check end_off themselves (`decode_tree` does
    exactly that)."""
    return _dec(buf, off, 0)


def _dec(buf: bytes, off: int, depth: int):
    if depth > _MAX_DEPTH:
        raise FrameCorruptError("wire tree deeper than the codec bound")
    tag = buf[off:off + 1]
    off += 1
    if tag == _T_NONE:
        return None, off
    if tag in (_T_LIST, _T_TUPLE):
        (n,) = struct.unpack_from(">I", buf, off)
        off += 4
        items = []
        for _ in range(n):
            child, off = _dec(buf, off, depth + 1)
            items.append(child)
        return (items if tag == _T_LIST else tuple(items)), off
    if tag == _T_DICT:
        (n,) = struct.unpack_from(">I", buf, off)
        off += 4
        d = {}
        for _ in range(n):
            (kl,) = struct.unpack_from(">H", buf, off)
            off += 2
            k = buf[off:off + kl].decode("utf-8")
            off += kl
            d[k], off = _dec(buf, off, depth + 1)
        return d, off
    if tag == _T_ARR:
        (dl,) = struct.unpack_from(">B", buf, off)
        off += 1
        dt = buf[off:off + dl].decode("ascii")
        off += dl
        (nd,) = struct.unpack_from(">B", buf, off)
        off += 1
        shape = struct.unpack_from(f">{nd}Q", buf, off)
        off += 8 * nd
        (rl,) = struct.unpack_from(">Q", buf, off)
        off += 8
        a = np.frombuffer(buf[off:off + rl],
                          dtype=np.dtype(dt)).reshape(shape)
        return a.copy(), off + rl
    raise FrameCorruptError(f"unknown wire tree tag {tag!r}")


# ---------------------------------------------------------------------------
# Trace context on the wire (ISSUE 15): an OPTIONAL suffix after the
# REQ frame's tree — tag "T", trace-id length+bytes, and the parent
# span id under which the worker's spans causally nest. STRICTLY
# absent when tracing is disabled: a disabled-mode REQ payload is
# byte-for-byte the pre-trace format, and the worker's ACK stays
# empty (an ACK for a TRACED request carries one f64 — the worker's
# perf_counter stamp the parent's clock-offset estimate needs).
# ---------------------------------------------------------------------------
def encode_trace_suffix(trace_id: str, parent=None) -> bytes:
    tb = str(trace_id).encode("ascii")
    if not tb or len(tb) > 255:
        raise ValueError(f"trace id length {len(tb)} not in [1, 255]")
    out = b"T" + struct.pack(">B", len(tb)) + tb
    if parent is None:
        return out + b"\x00"
    return out + b"\x01" + struct.pack(">Q", int(parent))


def decode_trace_suffix(buf: bytes, off: int):
    """(trace_id, parent) from the optional suffix at `off`; (None,
    None) when the payload ends there (untraced request). Anything
    else is structural damage."""
    if off == len(buf):
        return None, None
    if buf[off:off + 1] != b"T":
        raise FrameCorruptError(
            f"{len(buf) - off} trailing bytes after the tree that are "
            "not a trace suffix: codec desync")
    off += 1
    (n,) = struct.unpack_from(">B", buf, off)
    off += 1
    tid = buf[off:off + n].decode("ascii")
    off += n
    (has_parent,) = struct.unpack_from(">B", buf, off)
    off += 1
    parent = None
    if has_parent:
        (parent,) = struct.unpack_from(">Q", buf, off)
        off += 8
    if off != len(buf):
        raise FrameCorruptError(
            f"{len(buf) - off} trailing bytes after the trace suffix")
    return tid, parent


def encode_req_payload(deadline_ms, batch, trace=None) -> bytes:
    """One REQ payload: f64 deadline + encoded arrays (+ the trace
    suffix IFF `trace` is given — `(trace_id, parent_span_id)`). The
    zero-extra-wire-bytes contract lives here: trace=None produces
    exactly the pre-trace byte layout."""
    dl = -1.0 if deadline_ms is None else float(deadline_ms)
    payload = struct.pack(">d", dl) + encode_tree(list(batch))
    if trace is not None:
        payload += encode_trace_suffix(trace[0], trace[1])
    return payload


def decode_req_payload(payload: bytes):
    """(deadline_ms_or_None, arrays, trace_id, parent) — the worker
    side of `encode_req_payload`."""
    (dl,) = struct.unpack_from(">d", payload, 0)
    arrays, off = decode_tree_prefix(payload, 8)
    tid, parent = decode_trace_suffix(payload, off)
    return (None if dl < 0 else dl), arrays, tid, parent


def encode_decode_payload(prompt, n_new, temperature, top_k, seed,
                          deadline_ms, trace=None) -> bytes:
    """One DECODE payload: the session params as a wire tree (+ the
    trace suffix IFF `trace` is given — the REQ contract verbatim:
    an untraced session adds zero wire bytes)."""
    payload = encode_tree({
        "prompt": np.asarray(prompt, np.int32),
        "n_new": int(n_new),
        "temperature": float(temperature),
        "top_k": int(top_k),
        "seed": int(seed),
        "deadline_ms": (None if deadline_ms is None
                        else float(deadline_ms)),
    })
    if trace is not None:
        payload += encode_trace_suffix(trace[0], trace[1])
    return payload


def decode_decode_payload(payload: bytes):
    """(params_dict, trace_id, parent) — the worker side of
    `encode_decode_payload`. Scalars come back as 0-d numpy arrays
    (the tree codec's scalar form); the engine coerces."""
    d, off = decode_tree_prefix(payload, 0)
    tid, parent = decode_trace_suffix(payload, off)
    return d, tid, parent


def encode_resume_payload(ckpt: Dict, trace=None) -> bytes:
    """One RESUME payload: the migration checkpoint tree (numpy
    arrays / scalars / None leaves only — `export_decode_sessions`'s
    documented contract) + the optional trace suffix."""
    payload = encode_tree(dict(ckpt))
    if trace is not None:
        payload += encode_trace_suffix(trace[0], trace[1])
    return payload


def decode_resume_payload(payload: bytes):
    ckpt, off = decode_tree_prefix(payload, 0)
    tid, parent = decode_trace_suffix(payload, off)
    return ckpt, tid, parent


# ---------------------------------------------------------------------------
# Structured error mapping: the worker's exact single-engine exception
# types survive the boundary, so the router's failover/shed/poison
# policies fire unchanged.
# ---------------------------------------------------------------------------
def encode_error(e: BaseException) -> Dict:
    if isinstance(e, export_cache.BucketOverflowError):
        kind = "overflow"
    elif isinstance(e, ServeDeadlineError):
        kind = "deadline"
    elif isinstance(e, ServeOverloadError):
        return {"kind": "overload", "msg": str(e),
                "retry_after_ms": float(e.retry_after_ms)}
    elif isinstance(e, ServeQueueFullError):
        kind = "queue_full"
    elif isinstance(e, ServePoisonedError):
        kind = "poisoned"
    elif isinstance(e, ServeClosedError):
        return {"kind": "closed", "msg": str(e),
                "counted": bool(getattr(e, "counted", False))}
    elif isinstance(e, ServeDispatchError):
        kind = "dispatch"
    else:
        return {"kind": "dispatch", "msg": f"{type(e).__name__}: {e}"}
    return {"kind": kind, "msg": str(e)}


def decode_error(d: Dict) -> BaseException:
    kind, msg = d.get("kind", "dispatch"), d.get("msg", "")
    if kind == "overflow":
        return export_cache.BucketOverflowError(msg)
    if kind == "deadline":
        return ServeDeadlineError(msg)
    if kind == "overload":
        return ServeOverloadError(
            msg, retry_after_ms=float(d.get("retry_after_ms", 1.0)))
    if kind == "queue_full":
        return ServeQueueFullError(msg)
    if kind == "poisoned":
        return ServePoisonedError(msg)
    if kind == "closed":
        e = ServeClosedError(msg)
        if d.get("counted"):
            e.counted = True
        return e
    if kind == "transport":
        return ProcTransportError(msg)
    return ServeDispatchError(msg)


# Parent-side serve-counter bucket for each decoded terminal error.
_ERR_TERMINAL = {
    "deadline": "expired",
    "poisoned": "poisoned",
    "dispatch": "failed",
    "closed": "failed",
    "transport": "failed",
}

# Decode-SESSION mirror buckets (the 4-equation books): an admission
# refusal maps overload -> shed; an admitted session's error frame
# maps deadline -> expired; everything else is failed. `completed`
# comes from the final REP, and migration is not a terminal at all.
_DECODE_ERR_TERMINAL = {
    "deadline": "expired",
    "overload": "shed",
}


# ---------------------------------------------------------------------------
# Parent-side request bookkeeping
# ---------------------------------------------------------------------------
class _Pending:
    __slots__ = ("reply", "gen", "acked", "ack_err", "ack_ev",
                 "ipc_abs", "sweep_failed", "claimed", "trace",
                 "t_send", "decode")

    def __init__(self, reply: ServeReply, gen: int):
        self.reply = reply
        self.gen = gen
        self.acked = False
        self.ack_err: Optional[BaseException] = None
        self.ack_ev = threading.Event()
        self.ipc_abs: Optional[float] = None
        self.sweep_failed = False  # future failed, frame still owed
        self.trace = None  # (trace_id, parent) on a traced request
        self.t_send: Optional[float] = None  # REQ send perf_counter
        # decode-tier SESSION (DECODE/RESUME): terminals mirror into
        # the decode books, not the forward ones, and TOK frames feed
        # the reply's stream while the entry stays pending
        self.decode = False
        # One-terminal arbiter for UN-ADMITTED requests: the
        # submit()-timeout path, the reader's ERR-refusal path, and
        # the death sweep can all race to mirror this request's
        # terminal bucket — whoever takes the claim (under _plock)
        # mirrors, everyone else stands down. (Admitted requests are
        # arbitrated by the reply future's first write instead.)
        self.claimed = False

    def take_claim(self) -> bool:
        """Must be called under the owner's _plock."""
        if self.claimed:
            return False
        self.claimed = True
        return True


class _Gen:
    """Per-worker-generation reconciliation ledger: at quiescence
    `admitted == frames + swept + migrated` exactly — an admitted
    request either produced a reply/error frame that arrived, was
    swept into `failed` when its generation died, or (decode sessions
    only) LEFT on a MIGRATE frame to resume elsewhere. `handshake`
    holds the worker's final counters when the generation drained
    cleanly (the BYE frame); a SIGKILLed generation has none, which
    is exactly why the parent-side ledger is the authoritative one."""

    __slots__ = ("admitted", "frames", "swept", "migrated", "ack_errs",
                 "handshake", "clean", "exit_code", "pid", "clock",
                 "clock_offset_us", "clock_rtt_s", "clock_wall_us")

    def __init__(self, pid: int):
        self.admitted = 0
        self.frames = 0
        self.swept = 0
        self.migrated = 0
        self.ack_errs = 0
        self.handshake: Optional[Dict] = None
        self.clean = False
        self.exit_code: Optional[int] = None
        self.pid = pid
        # monotonic-clock alignment (ISSUE 15/18): worker
        # perf_counter + offset = parent perf_counter. Primary
        # estimate from the REQ->ACK handshake via
        # `trace.OffsetEstimator` (median over the smallest-RTT
        # samples, so network jitter and injected asymmetric delay
        # are filtered, not averaged in); fallback from the
        # heartbeat's (wall, mono) pair when no traced request has
        # round-tripped this generation yet.
        self.clock = trace_mod.OffsetEstimator()
        self.clock_offset_us: Optional[float] = None
        self.clock_rtt_s: Optional[float] = None
        self.clock_wall_us: Optional[float] = None

    def offset_us(self) -> float:
        if self.clock_offset_us is not None:
            return self.clock_offset_us
        if self.clock_wall_us is not None:
            return self.clock_wall_us
        return 0.0


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker_env() -> Dict[str, str]:
    """The environment a local worker subprocess starts in: the
    parent's, plus PYTHONPATH, plus the platform pin.

    One process per chip: a process that has initialised jax on the
    TPU holds it, and a worker that needs the same device would fail
    or hang until `spawn_timeout_s`. So a parent that holds the TPU is
    refused here, at once. A parent that forced a platform through
    `jax.config` (tier-1 forces cpu) hands it on as JAX_PLATFORMS —
    children cannot inherit a config update — and asking the config
    creates no backend, unlike `jax.default_backend()`."""
    import jax

    from .device import backend_initialized

    if backend_initialized() and jax.default_backend() == "tpu":
        raise RuntimeError(
            "one process per chip: this process has initialised jax "
            "on the TPU and holds it, so a ProcReplica worker could "
            "never get the device. On a TPU host run ONE process with "
            "transport='engine' — one EngineReplica per device "
            "(device.create_replica_device(i)). Worker subprocesses "
            "are for a parent that stays off the TPU (JAX_PLATFORMS="
            "cpu mechanics runs).")
    env = dict(os.environ)
    env["PYTHONPATH"] = (_repo_root() + os.pathsep
                         + env.get("PYTHONPATH", ""))
    if not env.get("JAX_PLATFORMS") and jax.config.jax_platforms:
        env["JAX_PLATFORMS"] = jax.config.jax_platforms
    return env


def resolve_factory(spec: Dict):
    """Import the spec's "module:callable" factory (after inserting
    its `sys_path` entries) — the one resolution both transports and
    the worker entrypoint share."""
    import importlib

    for p in spec.get("sys_path") or []:
        if p not in sys.path:
            sys.path.insert(0, p)
    mod_name, _, fn_name = str(spec.get("factory", "")).partition(":")
    if not fn_name:
        raise ValueError(
            f"spec factory {spec.get('factory')!r} must be "
            "'module:callable'")
    return getattr(importlib.import_module(mod_name), fn_name)


def _jsonable_spec(spec: Dict) -> Dict:
    """The spec crosses the boundary as JSON; FaultInjector schedules
    are documented as sets of step ordinals, which json refuses —
    normalize them (FaultInjector accepts any iterable back)."""
    out = dict(spec)
    inj = out.get("injector")
    if inj:
        inj = dict(inj)
        sched = {}
        for k, v in (inj.get("schedule") or {}).items():
            if isinstance(v, (set, frozenset, tuple)):
                v = sorted(int(s) for s in v)
            sched[k] = v
        inj["schedule"] = sched
        out["injector"] = inj
    return out


class ProcReplica:
    """A serving replica living in its OWN worker process, behind the
    exact `Replica` protocol `fleet.FleetRouter` speaks (start/kill/
    drain_stop/restart/submit/health/depth/warmup/killed + the chaos
    hooks) — the router cannot tell it from an `EngineReplica`, which
    is the whole point.

    `spec` names everything the worker needs to rebuild the replica
    deterministically (so a respawn is bit-identical and, with the
    shared store armed, deserialize-only):

      factory         "module:callable" returning a COMPILED eval-mode
                      Model (the `tools/prewarm.py --factory` idiom)
      factory_kwargs  keyword args for it (e.g. device_index, seed)
      sys_path        extra sys.path entries for the import
      engine          ServingEngine kwargs (max_batch, max_wait_ms,
                      shed_watermark, health_file, ...)
      injector        {"seed", "schedule", "hang_s"} rebuilt into a
                      worker-side `resilience.FaultInjector`
      export_cache    store dir (default: the parent's armed store —
                      the populate-once-start-N contract)
      buckets         device.set_shape_buckets kwargs for the worker
      quant           inference quant mode ("int8") armed at worker
                      boot BEFORE the engine builds (ISSUE 19) —
                      every replica of a fleet must share it so
                      MIGRATE/RESUME KV stays one form
      metrics_path    worker-side serving metrics JSONL (read it back
                      with `trace.read_metrics`; flush-per-record, so
                      a SIGKILLed worker leaves a parseable log)

    Transport modes (ISSUE 18) — the same `Replica` protocol over
    three launch/dial topologies:

      spawn    (default) today's behavior, unchanged: the parent binds
               an ephemeral loopback listener, spawns the worker with
               the spec in its env, and the connection IS the process
               — EOF means child death.
      listen   the parent binds a routable `host:port` and keeps
               accepting; the worker is launched ANYWHERE via
               `python -m singa_tpu.fleet_worker --connect host:port
               --token ...` (`launch="local"` makes the parent launch
               it locally — the hermetic test/bench arrangement;
               `launch="none"` waits for an external one). The spec
               ships over the wire in the WELCOME frame when the
               worker's HELLO asks (`need_spec`).
      connect  the parent DIALS an already-running worker started
               with `--listen host:port`.

    In the TCP modes socket EOF no longer implies child death: the
    generation gets a bounded `reconnect_window_s` during which
    in-flight requests fail over (PR 11 machinery — never hang, never
    double-deliver) and a reconnect carrying the current generation
    FENCE resumes the same generation with fresh per-direction frame
    sequence numbers; a stale fence is refused loudly (FENCED frame,
    `stale_reconnects_refused`). Window expiry flips `killed` and the
    supervisor's restart story takes over.

    Transport knobs (constructor kwargs, defaulting to the
    `device.set_fleet` process config): `ipc_deadline_ms`,
    `heartbeat_interval_s`, `spawn_timeout_s`, `max_inflight`,
    `reconnect_window_s`, `max_frame_bytes`."""

    def __init__(self, name: str, spec: Dict, *,
                 ipc_deadline_ms: Optional[float] = None,
                 heartbeat_interval_s: Optional[float] = None,
                 spawn_timeout_s: Optional[float] = None,
                 max_inflight: Optional[int] = None,
                 python: Optional[str] = None,
                 mode: str = "spawn",
                 host: str = "127.0.0.1",
                 port: int = 0,
                 launch: str = "local",
                 reconnect_window_s: Optional[float] = None,
                 max_frame_bytes: Optional[int] = None,
                 net_chaos: Optional[Dict] = None):
        from . import fleet

        cfg = fleet.get_config()
        self.name = str(name)
        self.spec = dict(spec)
        if mode not in ("spawn", "listen", "connect"):
            raise ValueError(
                f"unknown ProcReplica mode {mode!r} "
                "(spawn|listen|connect)")
        if launch not in ("local", "none"):
            raise ValueError(
                f"unknown ProcReplica launch {launch!r} (local|none)")
        self._mode = mode
        self._host = str(host)
        self._port = int(port)
        self._launch = launch if mode == "listen" else "none"
        if mode == "spawn":
            self._launch = "local"
        self.reconnect_window_s = float(
            reconnect_window_s if reconnect_window_s is not None
            else cfg.get("reconnect_window_s", 10.0))
        self.max_frame_bytes = int(
            max_frame_bytes if max_frame_bytes is not None
            else cfg.get("max_frame_bytes", _MAX_PAYLOAD))
        self._net_chaos = dict(net_chaos) if net_chaos else None
        if self._net_chaos is not None and mode != "listen":
            raise ValueError(
                "net_chaos needs mode='listen' (the proxy fronts the "
                "parent's listener)")
        if "factory" not in self.spec:
            raise ValueError(
                "ProcReplica spec needs a 'factory' (module:callable) "
                "— the worker must rebuild the model deterministically")
        self.ipc_deadline_s = float(
            ipc_deadline_ms if ipc_deadline_ms is not None
            else cfg["ipc_deadline_ms"]) / 1e3
        self.heartbeat_interval_s = float(
            heartbeat_interval_s if heartbeat_interval_s is not None
            else cfg["heartbeat_interval_s"])
        self.spawn_timeout_s = float(
            spawn_timeout_s if spawn_timeout_s is not None
            else cfg["spawn_timeout_s"])
        self.max_inflight = int(max_inflight if max_inflight is not None
                                else cfg["max_inflight"])
        if self.max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        self._python = python or sys.executable
        self.killed = False
        self.restarts = 0
        self.engine = None  # protocol parity: no in-process engine
        self._proc: Optional[subprocess.Popen] = None
        self._sock: Optional[socket.socket] = None
        self._reader: Optional[threading.Thread] = None
        self._wlock = threading.Lock()
        self._plock = threading.Lock()  # pending/gen bookkeeping
        self._pending: Dict[int, _Pending] = {}
        self._ctrl_pending: Dict[int, Dict] = {}
        self._next_id = 0
        self._gen = 0
        self._gens: Dict[int, _Gen] = {}
        self._hb: Optional[Dict] = None
        self._hb_rx = 0.0
        self._frozen_snap: Optional[Dict] = None
        self._frozen_until = 0.0
        self._stall_s = 0.0
        self._draining = False
        # TCP transport state (ISSUE 18). The fence is the parent's
        # generation-epoch counter: bumped on every FRESH adoption, it
        # is handed to the worker in WELCOME and must be echoed by
        # every reconnect HELLO — a stale/replayed connection carries
        # yesterday's fence and is refused, so a superseded worker can
        # never resurrect its generation. The token is stable for the
        # replica's lifetime in TCP modes (a remotely launched worker
        # cannot learn a fresh one per spawn).
        import secrets

        self._fence = 0
        self._tx_seq = 0
        self._token = str(self.spec.get("token")
                          or secrets.token_hex(16))
        self._lsock: Optional[socket.socket] = None
        self._listen_addr: Optional[Tuple[str, int]] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._proxy = None  # netchaos.ChaosProxy when net_chaos armed
        self._proxy_final = None  # last snapshot, kept across stop()
        self._superseded: set = set()  # old socks a reconnect replaced
        self._reconnecting = False
        self._reconnect_deadline = 0.0
        self._established = threading.Event()
        # lifetime transport counters (reconcile_transport reads them)
        self.sent = 0
        self.delivered = 0
        self.err_replies = 0
        self.transport_failed = 0
        self.torn_frames_detected = 0
        self.replay_frames_detected = 0
        self.gap_frames_detected = 0
        self.ipc_timeouts = 0
        self.hb_received = 0
        self.reconnects = 0
        self.reconnect_windows = 0
        self.stale_reconnects_refused = 0
        # decode-tier lane (ISSUE 17): its own sent/terminal counters
        # so the forward parent-terminals equation is untouched; at
        # quiescence decode_sent == decode_delivered +
        # decode_err_replies + decode_transport_failed + migrated_out.
        self.decode_sent = 0
        self.decode_delivered = 0
        self.decode_err_replies = 0
        self.decode_transport_failed = 0
        self.migrated_out = 0
        self.decode_tokens = 0
        # shipped worker spans (ISSUE 15): raw worker-clock records
        # piggybacked on REP/HB/BYE frames, kept per generation for
        # `trace_source()` to hand `trace.merge_chrome_traces` with
        # that generation's clock offset. Bounded deque; overflow
        # drops the OLDEST and counts it (O(1) — a list.pop(0) here
        # would memmove 8k entries under _plock on the reader's hot
        # path once full).
        from collections import deque

        self._shipped: "deque" = deque()
        self.spans_received = 0
        self.spans_dropped = 0

    # -- lifecycle --------------------------------------------------------
    @property
    def _tcp(self) -> bool:
        return self._mode != "spawn"

    def start(self) -> "ProcReplica":
        if self._mode == "listen":
            return self._start_listen()
        if self._mode == "connect":
            return self._start_connect()
        return self._start_spawn()

    def _start_spawn(self) -> "ProcReplica":
        if self._proc is not None and self._proc.poll() is None:
            self.killed = False
            return self
        import secrets

        token = secrets.token_hex(16)
        lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            lsock.bind(("127.0.0.1", 0))
            lsock.listen(1)
            port = lsock.getsockname()[1]
            spec = _jsonable_spec(self.spec)
            spec.setdefault("name", self.name)
            spec["port"] = port
            spec["token"] = token
            spec["heartbeat_interval_s"] = self.heartbeat_interval_s
            if trace_mod.enabled():
                # arm the worker's tracer + span ship-back at spawn —
                # and at every supervisor RESPAWN, since restart()
                # re-enters here: a new generation keeps propagating
                # the same trace contexts. (An explicit spec "trace"
                # wins — tests pin tiny ship buffers through it.)
                spec.setdefault("trace", {
                    "enabled": True, "ship_capacity": 2048,
                    "ring_capacity":
                        trace_mod.get_config()["ring_capacity"]})
            if "export_cache" not in spec:
                # inherit the parent's armed store: the populate-
                # once-start-N contract — a respawned worker
                # deserializes from the same artifacts the parent
                # prewarmed
                spec["export_cache"] = export_cache.directory()
            env = _worker_env()
            if spec.get("export_cache"):
                env["SINGA_TPU_EXPORT_CACHE"] = spec["export_cache"]
            env["SINGA_TPU_FLEET_SPEC"] = json.dumps(spec)
            self._proc = subprocess.Popen(
                [self._python, "-m", "singa_tpu.fleet_worker"],
                env=env, cwd=_repo_root(), stdout=subprocess.DEVNULL)
            lsock.settimeout(self.spawn_timeout_s)
            try:
                conn, _ = lsock.accept()
            except socket.timeout:
                raise ProcTransportError(
                    f"worker {self.name} did not connect within "
                    f"{self.spawn_timeout_s}s (exit code "
                    f"{self._proc.poll()})")
        finally:
            lsock.close()
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn.settimeout(self.spawn_timeout_s)
        reader = FrameReader(max_frame_bytes=self.max_frame_bytes,
                             check_seq=True)
        hello = None
        stashed: List[Tuple[int, int, bytes]] = []
        deadline = time.perf_counter() + self.spawn_timeout_s
        while hello is None:
            if time.perf_counter() > deadline:
                raise ProcTransportError(
                    f"worker {self.name}: no HELLO within "
                    f"{self.spawn_timeout_s}s")
            chunk = conn.recv(65536)
            if not chunk:
                raise ProcTransportError(
                    f"worker {self.name} closed before HELLO (exit "
                    f"code {self._proc.poll()})")
            for ftype, rid, payload in reader.feed(chunk):
                if ftype == HELLO and hello is None:
                    hello = json.loads(payload.decode("utf-8"))
                else:
                    # frames coalesced behind HELLO in one chunk —
                    # the worker's immediate first heartbeat usually
                    # rides here; dropping it would boot every fresh
                    # worker stale
                    stashed.append((ftype, rid, payload))
        if hello.get("token") != token:
            self._proc.kill()
            raise ProcTransportError(
                f"worker {self.name}: HELLO token mismatch")
        self._gen += 1
        gen = self._gen
        self._gens[gen] = _Gen(pid=int(hello.get("pid", -1)))
        with self._wlock:
            self._tx_seq = 0  # fresh connection: both directions at 0
        self._sock = conn
        self.killed = False
        self._draining = False
        conn.settimeout(0.05)
        for ftype, rid, payload in stashed:
            try:
                self._handle_frame(ftype, rid, payload, gen)
            except Exception:
                pass
        self._reader = threading.Thread(
            target=self._read_loop, args=(conn, reader, gen),
            name=f"singa_tpu-proc-{self.name}", daemon=True)
        self._reader.start()
        # The worker sends its first heartbeat right behind HELLO:
        # wait for it so a fresh (or respawned) replica enters the
        # rotation READY instead of spending a stale-ejection round
        # trip on its own boot.
        deadline = time.perf_counter() + min(5.0, self.spawn_timeout_s)
        while self._hb is None and time.perf_counter() < deadline:
            time.sleep(0.002)
        return self

    # -- TCP transport modes (ISSUE 18) -----------------------------------
    def listen_addr(self) -> Tuple[str, int]:
        """The address a worker must `--connect` to: the ChaosProxy's
        front door when net chaos is armed, else the raw listener."""
        if self._proxy is not None:
            return self._proxy.addr
        if self._listen_addr is None:
            raise RuntimeError(f"replica {self.name} is not listening")
        return self._listen_addr

    def net_chaos_snapshot(self) -> Optional[Dict]:
        """The armed `ChaosProxy`'s counter snapshot (frames seen,
        partitions/delays/reorders/dups/drips injected); None when no
        net chaos is armed. Bench reads this to prove the injected
        frame-fault RATE, not just that faults were scheduled. After
        `stop(final=True)` tears the proxy down, the LAST snapshot
        stays readable — evidence survives shutdown."""
        px = self._proxy
        return self._proxy_final if px is None else px.snapshot()

    def _ensure_listener(self) -> None:
        if self._lsock is not None:
            return
        lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lsock.bind((self._host, self._port))
        lsock.listen(4)
        self._lsock = lsock
        self._listen_addr = lsock.getsockname()[:2]
        if self._net_chaos is not None and self._proxy is None:
            from . import netchaos

            # the proxy IS the network between parent and worker: it
            # persists across worker generations and reconnects
            self._proxy = netchaos.ChaosProxy(
                upstream=self._listen_addr, **self._net_chaos).start()
        t = threading.Thread(target=self._accept_loop, args=(lsock,),
                             name=f"singa_tpu-accept-{self.name}",
                             daemon=True)
        self._accept_thread = t
        t.start()

    def _start_listen(self) -> "ProcReplica":
        if self._sock is not None and not self.killed:
            return self
        self._ensure_listener()
        self._established.clear()
        with self._plock:
            self._reconnecting = False
        self.killed = False
        self._draining = False
        if self._launch == "local" and (
                self._proc is None or self._proc.poll() is not None):
            self._launch_local_worker()
        if not self._established.wait(self.spawn_timeout_s):
            code = None if self._proc is None else self._proc.poll()
            raise ProcTransportError(
                f"worker {self.name}: no authenticated connection on "
                f"{self.listen_addr()} within {self.spawn_timeout_s}s "
                f"(local worker exit code {code})")
        deadline = time.perf_counter() + min(5.0, self.spawn_timeout_s)
        while self._hb is None and time.perf_counter() < deadline:
            time.sleep(0.002)
        return self

    def _start_connect(self) -> "ProcReplica":
        if self._sock is not None and not self.killed:
            return self
        self._established.clear()
        with self._plock:
            self._reconnecting = False
        self.killed = False
        self._draining = False
        try:
            conn = socket.create_connection(
                (self._host, self._port), timeout=self.spawn_timeout_s)
        except OSError as e:
            raise ProcTransportError(
                f"replica {self.name}: cannot dial worker at "
                f"{self._host}:{self._port} ({e})")
        try:
            self._tcp_handshake(conn)
        except Exception:
            try:
                conn.close()
            except OSError:
                pass
            raise
        deadline = time.perf_counter() + min(5.0, self.spawn_timeout_s)
        while self._hb is None and time.perf_counter() < deadline:
            time.sleep(0.002)
        return self

    def _launch_local_worker(self) -> None:
        """The `listen`-mode local launch: the worker gets ONLY the
        CLI a remote host would get (`--connect host:port --token`) —
        no spec in its env, so the WELCOME spec-shipping path is
        exercised on every hermetic run — plus the env hygiene any
        launch recipe needs (PYTHONPATH, backend pin, store dir)."""
        env = _worker_env()
        store = self.spec.get("export_cache") or export_cache.directory()
        if store:
            env["SINGA_TPU_EXPORT_CACHE"] = store
        env.pop("SINGA_TPU_FLEET_SPEC", None)
        host, port = self.listen_addr()
        self._proc = subprocess.Popen(
            [self._python, "-m", "singa_tpu.fleet_worker",
             "--connect", f"{host}:{port}", "--token", self._token,
             "--name", self.name],
            env=env, cwd=_repo_root(), stdout=subprocess.DEVNULL)

    def _accept_loop(self, lsock: socket.socket) -> None:
        while self._lsock is lsock:
            try:
                conn, _ = lsock.accept()
            except OSError:
                return  # listener closed: replica stopped
            try:
                self._tcp_handshake(conn)
            except Exception:
                try:
                    conn.close()
                except OSError:
                    pass

    def _tcp_handshake(self, conn: socket.socket) -> None:
        """Authenticate + fence one inbound/dialed connection. The
        worker speaks first (HELLO {token, fence, need_spec, ...});
        the parent answers WELCOME (adopt or resume) or FENCED
        (refuse) and only then puts the connection in service."""
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn.settimeout(min(10.0, self.spawn_timeout_s))
        reader = FrameReader(max_frame_bytes=self.max_frame_bytes,
                             check_seq=True)
        hello = None
        stashed: List[Tuple[int, int, bytes]] = []
        deadline = time.perf_counter() + self.spawn_timeout_s
        while hello is None:
            if time.perf_counter() > deadline:
                raise ProcTransportError(
                    f"worker {self.name}: no HELLO within "
                    f"{self.spawn_timeout_s}s")
            try:
                chunk = conn.recv(65536)
            except socket.timeout:
                continue
            if not chunk:
                raise ProcTransportError(
                    f"worker {self.name}: connection closed before "
                    "HELLO")
            for ftype, rid, payload in reader.feed(chunk):
                if ftype == HELLO and hello is None:
                    hello = json.loads(payload.decode("utf-8"))
                else:
                    stashed.append((ftype, rid, payload))
        if hello.get("token") != self._token:
            self._refuse(conn, "auth token mismatch")
            raise ProcTransportError(
                f"worker {self.name}: HELLO token mismatch")
        fence = hello.get("fence")
        with self._plock:
            live = self._sock is not None
            resumable = (self._reconnecting and not self.killed
                         and time.perf_counter()
                         < self._reconnect_deadline)
        if fence is None:
            # fresh adoption: a brand-new worker generation
            if live:
                self._refuse(conn, "a live connection already serves "
                                   "the current generation")
                raise ProcTransportError(
                    f"worker {self.name}: second fresh HELLO while a "
                    "connection is live")
            with self._plock:
                self._fence += 1
                self._gen += 1
                gen = self._gen
                self._gens[gen] = _Gen(pid=int(hello.get("pid", -1)))
                self._reconnecting = False
            welcome = {"fence": self._fence, "gen": gen,
                       "reconnect_window_s": self.reconnect_window_s}
            if hello.get("need_spec"):
                spec = _jsonable_spec(self.spec)
                spec.setdefault("name", self.name)
                spec["heartbeat_interval_s"] = self.heartbeat_interval_s
                if trace_mod.enabled():
                    spec.setdefault("trace", {
                        "enabled": True, "ship_capacity": 2048,
                        "ring_capacity":
                            trace_mod.get_config()["ring_capacity"]})
                if "export_cache" not in spec:
                    spec["export_cache"] = export_cache.directory()
                spec.pop("token", None)
                spec.pop("port", None)
                welcome["spec"] = spec
            self._wire_up(conn, reader, gen, welcome, stashed)
            return
        if int(fence) == self._fence and not self.killed:
            # Same-generation reconnect: the fence (token-authed) is
            # the authority, not the parent's view of the old socket —
            # the worker sees an inbound fault FIRST and redials
            # before the parent has noticed anything wrong. The newer
            # connection supersedes the old one: its in-flight
            # requests fail over NOW (PR 11 machinery; replies the
            # worker resends for them dedup by rid, so nothing
            # double-delivers) and the old reader's eventual
            # conn-lost is a recorded no-op.
            with self._plock:
                gen = self._gen
                self._reconnecting = False
                old, self._sock = self._sock, None
                if old is not None:
                    self._superseded.add(old)
            if old is not None:
                try:
                    old.close()
                except OSError:
                    pass
                self._fail_all_pending(ProcTransportError(
                    f"worker {self.name} (gen {gen}): connection "
                    "superseded by a same-generation reconnect; "
                    "in-flight requests fail over"))
            self.reconnects += 1
            self._wire_up(conn, reader, gen,
                          {"fence": self._fence, "gen": gen,
                           "reconnect_window_s":
                               self.reconnect_window_s,
                           "resumed": True}, stashed)
            return
        # stale (or out-of-window) generation fence: refuse LOUDLY —
        # a replayed/superseded connection must never resurrect a
        # generation the supervisor has moved past
        self.stale_reconnects_refused += 1
        self._refuse(conn, f"stale generation fence {fence} "
                           f"(current {self._fence}, "
                           f"window={'open' if resumable else 'closed'})")
        raise ProcTransportError(
            f"worker {self.name}: stale-generation reconnect refused "
            f"(fence {fence}, current {self._fence})")

    def _refuse(self, conn: socket.socket, reason: str) -> None:
        try:
            send_frame(conn, encode_frame(
                FENCED, 0,
                json.dumps({"reason": reason}).encode("utf-8"),
                seq=0), deadline_s=2.0)
        except OSError:
            pass
        try:
            conn.close()
        except OSError:
            pass

    def _wire_up(self, conn: socket.socket, reader: FrameReader,
                 gen: int, welcome: Dict, stashed) -> None:
        with self._wlock:
            self._tx_seq = 0  # fresh connection: both directions at 0
        self._sock = conn
        self.killed = False
        conn.settimeout(0.05)
        self._send(WELCOME, 0,
                   json.dumps(welcome).encode("utf-8"))
        for ftype, rid, payload in stashed:
            try:
                self._handle_frame(ftype, rid, payload, gen)
            except Exception:
                pass
        self._reader = threading.Thread(
            target=self._read_loop, args=(conn, reader, gen),
            name=f"singa_tpu-proc-{self.name}", daemon=True)
        self._reader.start()
        self._established.set()

    def _reconnect_active(self) -> bool:
        """True while the bounded reconnect window is open. On expiry
        the generation is DECLARED dead (killed=True) — the supervisor
        restart story takes over — and a lingering local worker is
        reaped so a later respawn cannot race two workers onto one
        device."""
        with self._plock:
            if not self._reconnecting:
                return False
            if time.perf_counter() < self._reconnect_deadline:
                return True
            self._reconnecting = False
        self.killed = True
        self.sigkill()  # no-op for an external worker (no local proc)
        return False

    def _alive(self) -> bool:
        if self.killed:
            return False
        if self._tcp:
            p = self._proc
            if p is not None and p.poll() is not None:
                return False  # local worker observably dead
            if self._sock is not None:
                return True
            return self._reconnect_active()
        return self._proc is not None and self._proc.poll() is None

    def kill(self) -> None:
        """Hard replica death: SIGKILL the worker. In-flight futures
        fail loudly (`ProcTransportError` => router failover), and the
        replica stays dead until `restart()` respawns it."""
        self.killed = True
        self.sigkill()
        self._reap(expected=False)

    def sigkill(self) -> None:
        """The raw chaos primitive (`proc_sigkill`): SIGKILL the
        worker and nothing else — detection (reader EOF, child exit
        code) and recovery (supervisor respawn) must be OBSERVED, not
        arranged."""
        p = self._proc
        if p is not None and p.poll() is None:
            try:
                os.kill(p.pid, signal.SIGKILL)
            except OSError:
                pass

    def drain_stop(self) -> None:
        """Router drain semantics: the worker stops admitting, fails
        its queued futures (`ServeClosedError` frames => the router
        reroutes them), ships its final counters (BYE), and exits 0.
        TCP listener/proxy stay up — a restart() re-adopts through
        them."""
        self._shutdown(drain=False, timeout=10.0)

    def stop(self, drain: bool = True) -> None:
        self._shutdown(drain=drain, timeout=max(
            10.0, self.spawn_timeout_s / 2), final=True)

    def _shutdown(self, drain: bool, timeout: float,
                  final: bool = False) -> None:
        p = self._proc
        if p is None and self._sock is None and not final:
            return
        self._draining = True
        alive = (p is not None and p.poll() is None) \
            or (p is None and self._sock is not None)
        if alive and self._sock is not None:
            try:
                self._send(CTRL, 0, json.dumps(
                    {"op": "drain", "drain": bool(drain)}
                ).encode("utf-8"))
            except Exception:
                pass
            if p is not None:
                try:
                    p.wait(timeout)
                except subprocess.TimeoutExpired:
                    # a hung dispatch must not block stop forever:
                    # kill, sweep, respawn is the supervisor's problem
                    self.sigkill()
            else:
                # external worker (connect / listen+launch=none): wait
                # for its BYE handshake or EOF, bounded — the parent
                # cannot reap a process it never owned
                dl = time.perf_counter() + timeout
                while time.perf_counter() < dl:
                    g = self._gens.get(self._gen)
                    if self._sock is None or (g is not None and g.clean):
                        break
                    time.sleep(0.02)
        self._reap(expected=True)
        if final:
            self._close_tcp()

    def _close_tcp(self) -> None:
        ls, self._lsock = self._lsock, None
        if ls is not None:
            try:
                ls.close()
            except OSError:
                pass
        self._listen_addr = None
        px, self._proxy = self._proxy, None
        if px is not None:
            # keep the final fault evidence readable after shutdown —
            # the bench reconciles proxy counters at quiescence
            self._proxy_final = px.snapshot()
            px.stop()

    def _reap(self, expected: bool) -> None:
        p, self._proc = self._proc, None
        if p is not None:
            try:
                p.wait(10.0)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(10.0)
            gen = self._gens.get(self._gen)
            if gen is not None and gen.exit_code is None:
                gen.exit_code = p.returncode
        t, self._reader = self._reader, None
        s, self._sock = self._sock, None
        if s is not None:
            try:
                s.close()
            except OSError:
                pass
        if t is not None and t is not threading.current_thread():
            t.join(5.0)
        self._fail_all_pending(ProcTransportError(
            f"worker {self.name} "
            + ("stopped" if expected else "died")
            + f" with the request in flight (gen {self._gen})"))
        if not expected:
            self.killed = True

    def restart(self) -> "ProcReplica":
        """Respawn a fresh worker from the same deterministic spec.
        With the shared store prewarmed the new generation's first
        dispatch of every bucket is a store LOAD — deserialize-only,
        provable from the heartbeat's export counters. TCP modes:
        `listen`+local relaunches the worker through the persistent
        listener (new generation, new fence); `connect` re-dials the
        external worker — which can only be re-adopted FRESH, its old
        fence is dead."""
        if self._proc is not None or self._sock is not None:
            self.sigkill()
            self._reap(expected=True)
        self.restarts += 1
        self._frozen_snap = None
        self._hb = None
        with self._plock:
            self._reconnecting = False
        return self.start()

    # -- request path -----------------------------------------------------
    def _send(self, ftype: int, rid: int, payload: bytes) -> None:
        """Serialize one frame onto the wire UNDER the write lock with
        the partial-write-hardened `send_frame` loop: the socket
        carries a short `settimeout`, and a bare `sendall` under one
        can write a PREFIX of a frame, raise `socket.timeout`, and let
        the next caller interleave its frame mid-frame — permanent
        stream corruption. `send_frame` retries short writes on the
        SAME frame to a deadline; if it still fails, bytes may be out,
        so the connection is poisoned (closed — the reader path then
        fails in-flight requests and, on TCP, opens the reconnect
        window) rather than reused."""
        sock = self._sock
        if sock is None:
            raise ServeClosedError(f"replica {self.name} is dead")
        with self._wlock:
            if self._sock is not sock:
                sock = self._sock  # reconnected under our feet
                if sock is None:
                    raise ServeClosedError(
                        f"replica {self.name} is dead")
            stall, self._stall_s = self._stall_s, 0.0
            if stall > 0:
                time.sleep(stall)  # injected pipe_stall: the write
                # path wedges while holding the pipe, exactly what a
                # full socket buffer looks like from the caller side
            frame = encode_frame(ftype, rid, payload,
                                 seq=self._tx_seq)
            try:
                send_frame(sock, frame,
                           deadline_s=min(self.ipc_deadline_s, 10.0))
            except OSError as e:
                self._poison_conn(sock)
                raise ServeClosedError(
                    f"replica {self.name}: pipe write failed ({e})")
            self._tx_seq += 1

    def _poison_conn(self, sock: socket.socket) -> None:
        """A frame may be HALF-written on this connection: it can
        never carry another frame. Shut it down so the reader thread
        observes the loss and runs the death/reconnect machinery."""
        if self._sock is sock:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    def submit(self, *arrays, deadline_ms: Optional[float] = None
               ) -> ServeReply:
        """Submit one request across the boundary. Admission is
        SYNCHRONOUS (REQ -> ACK within the IPC deadline), so every
        submit-time refusal keeps its exact single-engine type and
        the parent's mirrored terminal counters stay one-bucket-per-
        request — the `fleet.reconcile` equations hold unchanged."""
        if not self._alive():
            raise ServeClosedError(f"replica {self.name} is dead")
        if self._tcp and self._sock is None:
            # reconnect window open: there is no pipe to put the
            # request on. Shed LOUDLY (mirrored requests+shed keeps
            # the engine equation exact) with a retry hint sized to
            # the window — the router's shed-aware retry lands it on
            # a healthy replica instead of stranding the caller here.
            note_remote_request()
            note_remote_terminal("shed")
            raise ServeOverloadError(
                f"replica {self.name}: transport reconnecting — "
                "no connection to admit on", retry_after_ms=50.0)
        batch = ServingEngine._as_batch(arrays)
        if not batch:
            raise ValueError("serve request needs at least one input")
        n = int(batch[0].shape[0])
        with self._plock:
            # decode sessions are long-lived streams with their own
            # admission control (the worker's KV-slot pool) — they
            # must not starve the forward lane's in-flight budget
            inflight = sum(1 for e in self._pending.values()
                           if not e.decode)
        if inflight >= self.max_inflight:
            # shed instead of ballooning the pipe: the hint is the
            # worker's own estimate from its last heartbeat
            note_remote_request()
            note_remote_terminal("shed")
            hint = 50.0
            hb = self._hb
            if hb and hb.get("retry_after_ms"):
                hint = float(hb["retry_after_ms"])
            raise ServeOverloadError(
                f"replica {self.name}: {inflight} requests in flight "
                f"at the transport bound ({self.max_inflight}); the "
                "pipe must not balloon — retry after the hinted "
                "backoff", retry_after_ms=hint)
        reply = ServeReply(n)
        with self._plock:
            self._next_id += 1
            rid = self._next_id
            ent = _Pending(reply, self._gen)
            self._pending[rid] = ent
        note_remote_request()
        # Trace context crosses the boundary as an OPTIONAL suffix:
        # with tracing off there is no context and the payload is
        # byte-for-byte the untraced format — zero extra wire bytes.
        trace = None
        if trace_mod.enabled():
            ctx = trace_mod.current_trace()
            if ctx is not None:
                trace = (ctx["trace_id"],
                         trace_mod.current_span_id() or ctx["parent"])
        ent.trace = trace
        payload = encode_req_payload(deadline_ms, batch, trace=trace)
        ent.t_send = time.perf_counter()
        try:
            self._send(REQ, rid, payload)
        except ServeClosedError:
            with self._plock:
                popped = self._pending.pop(rid, None)
                claim = popped is not None and popped.take_claim()
            if claim:
                note_remote_terminal("failed")
            err = ServeClosedError(
                f"replica {self.name} died before the request was "
                "admitted")
            err.counted = True
            raise err
        if not ent.ack_ev.wait(self.ipc_deadline_s):
            # no admission verdict in time: fail THIS caller loudly
            # and keep the ledger exact — if the worker later admits
            # it, the late ACK/REP land on the already-failed future
            # and are dropped (first write wins), counted as frames.
            with self._plock:
                claim = ent.take_claim()
            self.ipc_timeouts += 1
            reply._fail(ProcTransportError(
                f"replica {self.name}: no admission ACK within "
                f"{self.ipc_deadline_s * 1e3:.0f} ms (worker hung "
                "or pipe stalled)"))
            if claim:
                # failed (never admitted): the request never entered
                # `sent`, so it must not enter `transport_failed` —
                # the parent-terminals equation covers ADMITTED
                # requests only; this one is a submit-time refusal
                # the router books as `refused`.
                note_remote_terminal("failed")
            err = ServeClosedError(
                f"replica {self.name}: admission timed out")
            err.counted = True
            raise err
        if ent.ack_err is not None:
            raise ent.ack_err
        # admitted: arm the in-flight IPC deadline (transport bound on
        # top of the caller's own deadline — the worker expires THAT)
        user_s = 0.0 if deadline_ms is None else float(deadline_ms) / 1e3
        ent.ipc_abs = time.perf_counter() + self.ipc_deadline_s + user_s
        self.sent += 1
        return reply

    def submit_decode(self, prompt_ids, max_new_tokens: int,
                      temperature: float = 0.0, top_k: int = 0,
                      seed: int = 0,
                      deadline_ms: Optional[float] = None) -> ServeReply:
        """Submit one generative session across the boundary
        (`ServingEngine.submit_decode`, DECODE frame). Admission is
        synchronous like `submit` — a refusal keeps its exact engine
        type (`ServeOverloadError.retry_after_ms` is the worker's own
        slot-pool hint) — and the returned reply's `tokens()` stream
        is fed by TOK frames as the worker's fused steps land, with
        the final REP delivering the full `[1, P + n]` array. A drain
        mid-stream fails the reply with `ServeMigratedError` carrying
        the checkpoint (MIGRATE frame) for re-placement."""
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None, :]
        if prompt.ndim != 2 or prompt.shape[0] != 1 \
                or prompt.shape[1] < 1:
            raise ValueError(
                f"decode prompt must be [P] or [1, P] token ids, got "
                f"shape {prompt.shape}")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        trace = None
        if trace_mod.enabled():
            ctx = trace_mod.current_trace()
            if ctx is not None:
                trace = (ctx["trace_id"],
                         trace_mod.current_span_id() or ctx["parent"])
        payload = encode_decode_payload(
            prompt, max_new_tokens, temperature, top_k, seed,
            deadline_ms, trace=trace)
        return self._decode_roundtrip(DECODE, payload, deadline_ms,
                                      trace)

    def resume_decode(self, ckpt: Dict) -> ServeReply:
        """Admit a migrated session's checkpoint on THIS replica
        (RESUME frame -> `ServingEngine.resume_decode`): the worker
        re-streams the ledger prefix through TOK frames first, then
        the live continuation — one seamless stream for a consumer
        that dedupes by count."""
        trace = None
        if trace_mod.enabled():
            ctx = trace_mod.current_trace()
            if ctx is not None:
                trace = (ctx["trace_id"],
                         trace_mod.current_span_id() or ctx["parent"])
        dl = ckpt.get("deadline_ms_left")
        payload = encode_resume_payload(ckpt, trace=trace)
        return self._decode_roundtrip(RESUME, payload,
                                      None if dl is None else float(
                                          np.asarray(dl)), trace)

    def _decode_roundtrip(self, ftype: int, payload: bytes,
                          deadline_ms: Optional[float],
                          trace) -> ServeReply:
        """The shared DECODE/RESUME admission dance — `submit`'s
        REQ -> ACK protocol with the terminal mirrors routed into the
        decode-session books (`note_remote_decode_*`) instead of the
        forward ones. Sessions do NOT count toward `max_inflight`
        (they are long-lived streams; the worker's KV-slot pool is
        their admission control) and carry no transport sweep deadline
        unless the session itself has one."""
        if not self._alive():
            raise ServeClosedError(f"replica {self.name} is dead")
        if self._tcp and self._sock is None:
            # reconnect window open: shed the session loudly, exactly
            # like the worker's own slot-pool refusal (sessions+shed
            # keeps the decode equation exact)
            note_remote_decode_session(resumed=(ftype == RESUME))
            note_remote_decode_terminal("shed")
            raise ServeOverloadError(
                f"replica {self.name}: transport reconnecting — "
                "no connection to admit the session on",
                retry_after_ms=50.0)
        reply = ServeReply(1)
        with self._plock:
            self._next_id += 1
            rid = self._next_id
            ent = _Pending(reply, self._gen)
            ent.decode = True
            self._pending[rid] = ent
        note_remote_decode_session(resumed=(ftype == RESUME))
        ent.trace = trace
        ent.t_send = time.perf_counter()
        try:
            self._send(ftype, rid, payload)
        except ServeClosedError:
            with self._plock:
                popped = self._pending.pop(rid, None)
                claim = popped is not None and popped.take_claim()
            if claim:
                note_remote_decode_terminal("failed")
            err = ServeClosedError(
                f"replica {self.name} died before the decode session "
                "was admitted")
            err.counted = True
            raise err
        if not ent.ack_ev.wait(self.ipc_deadline_s):
            with self._plock:
                claim = ent.take_claim()
            self.ipc_timeouts += 1
            reply._fail(ProcTransportError(
                f"replica {self.name}: no decode admission ACK within "
                f"{self.ipc_deadline_s * 1e3:.0f} ms (worker hung or "
                "pipe stalled)"))
            if claim:
                note_remote_decode_terminal("failed")
            err = ServeClosedError(
                f"replica {self.name}: decode admission timed out")
            err.counted = True
            raise err
        if ent.ack_err is not None:
            raise ent.ack_err
        if deadline_ms is not None:
            # transport bound past the session's own deadline — the
            # worker expires THAT; a deadline-free session is bounded
            # by its token budget, not by the IPC sweep
            ent.ipc_abs = (time.perf_counter() + self.ipc_deadline_s
                           + float(deadline_ms) / 1e3)
        self.decode_sent += 1
        return reply

    def warmup(self, *arrays) -> int:
        batch = ServingEngine._as_batch(arrays)
        res = self._ctrl_sync(WARM, encode_tree(list(batch)),
                              timeout=self.spawn_timeout_s)
        return int(res.get("warmed", 0))

    def warm_decode(self, prompt_lens=(), max_new_tokens=None,
                    samplers=()) -> int:
        """Worker-side `ServingEngine.warm_decode` over the wire: with
        the shared store prewarmed this is deserialize-only — the
        respawn-readiness probe the decode tier's restart story pins
        (store hits >= 1, traces == 0, from `counters()`)."""
        res = self._ctrl_sync(CTRL, json.dumps(
            {"op": "warm_decode",
             "prompt_lens": [int(p) for p in prompt_lens],
             "max_new_tokens": max_new_tokens,
             "samplers": [[float(t), int(k)] for t, k in samplers]}
            ).encode("utf-8"),
            timeout=self.spawn_timeout_s)
        return int(res.get("warmed", 0))

    def counters(self, timeout: float = 5.0) -> Dict:
        """Live reconciliation probe: the worker's CURRENT terminal +
        export counters (the same payload the BYE handshake ships)."""
        return self._ctrl_sync(
            CTRL, json.dumps({"op": "counters"}).encode("utf-8"),
            timeout=timeout)

    def _ctrl_sync(self, ftype: int, payload: bytes,
                   timeout: float) -> Dict:
        if not self._alive():
            raise ServeClosedError(f"replica {self.name} is dead")
        ev = threading.Event()
        box: Dict = {}
        with self._plock:
            self._next_id += 1
            rid = self._next_id
            self._ctrl_pending[rid] = {"ev": ev, "box": box}
        try:
            self._send(ftype, rid, payload)
            if not ev.wait(timeout):
                raise ProcTransportError(
                    f"replica {self.name}: control round-trip timed "
                    f"out after {timeout}s")
        finally:
            with self._plock:
                self._ctrl_pending.pop(rid, None)
        return box.get("result", {})

    # -- health/load signals ----------------------------------------------
    def health(self) -> Dict:
        """The last HEARTBEAT's health snapshot, with the worker's own
        wall-clock stamp — a dead or wedged worker stops refreshing
        it, the snapshot ages, and the router's stale-snapshot
        ejection fires (missed heartbeat => stale => fail closed,
        the PR 11 path verbatim)."""
        if (self._frozen_snap is not None
                and time.perf_counter() < self._frozen_until):
            return dict(self._frozen_snap)
        if not self._alive():
            g = self._gens.get(self._gen)
            code = None if g is None else g.exit_code
            return {"state": "unhealthy",
                    "reasons": [f"worker {self.name} dead (exit code "
                                f"{code})"],
                    "time": round(time.time(), 3), "name": self.name}
        if self._tcp and self._sock is None:
            # reconnect window open: fail closed NOW (the router
            # ejects and routes around) — an unstamped snapshot also
            # reads as stale, so both freshness paths agree
            return {"state": "unhealthy",
                    "reasons": ["connection lost; reconnect window "
                                "open"],
                    "name": self.name}
        hb = self._hb
        if hb is None:
            # spawned but no heartbeat yet: an unstamped snapshot
            # reads as stale — fail closed until the worker proves
            # itself
            return {"state": "unhealthy",
                    "reasons": ["no heartbeat received yet"],
                    "name": self.name}
        snap = dict(hb.get("health") or {})
        snap.setdefault("name", self.name)
        return snap

    def depth(self) -> int:
        with self._plock:
            return len(self._pending)

    def slo_probe(self) -> Dict:
        """Anomaly-detector inputs for the router's SLO tick (ISSUE
        20): heartbeat age and the current generation's clock offset
        next to the transport's OWN uncertainty estimate — the
        detector thresholds on what the estimator admits it doesn't
        know, not on a magic constant."""
        out: Dict = {"hb_gap_s": None, "clock_offset_us": None,
                     "clock_uncertainty_us": None}
        if self._hb_rx:  # 0.0 == no heartbeat yet: nothing to gap
            out["hb_gap_s"] = time.perf_counter() - self._hb_rx
        with self._plock:
            g = self._gens.get(self._gen)
            if g is not None and g.clock_offset_us is not None:
                out["clock_offset_us"] = g.clock_offset_us
                out["clock_uncertainty_us"] = g.clock.uncertainty_us()
        return out

    def device_token(self):
        """Two workers pinned to one device id would contend for the
        same chip under load — surface it at fleet construction (the
        router's shared-device warning), not as mystery latency."""
        idx = (self.spec.get("factory_kwargs") or {}).get(
            "device_index")
        return None if idx is None else ("proc-device", int(idx))

    def transport_snapshot(self) -> Dict:
        """Lifetime transport counters + per-generation ledger (the
        `fleet.reconcile_transport` input)."""
        with self._plock:
            gens = {
                g: {"admitted": gen.admitted, "frames": gen.frames,
                    "swept": gen.swept, "migrated": gen.migrated,
                    "ack_errs": gen.ack_errs,
                    "clean": gen.clean, "exit_code": gen.exit_code,
                    "handshake": gen.handshake,
                    "pid": gen.pid,
                    "clock_offset_us": gen.clock_offset_us,
                    "clock_rtt_s": gen.clock_rtt_s,
                    "clock_uncertainty_us": gen.clock.uncertainty_us()}
                for g, gen in self._gens.items()}
            return {
                "sent": self.sent,
                "delivered": self.delivered,
                "err_replies": self.err_replies,
                "transport_failed": self.transport_failed,
                "ipc_timeouts": self.ipc_timeouts,
                "torn_frames_detected": self.torn_frames_detected,
                "replay_frames_detected": self.replay_frames_detected,
                "gap_frames_detected": self.gap_frames_detected,
                "pending": len(self._pending),
                "heartbeats": self.hb_received,
                "mode": self._mode,
                "fence": self._fence,
                "reconnects": self.reconnects,
                "reconnect_windows": self.reconnect_windows,
                "stale_reconnects_refused":
                    self.stale_reconnects_refused,
                "spans_received": self.spans_received,
                "spans_dropped": self.spans_dropped,
                "decode": {
                    "sent": self.decode_sent,
                    "delivered": self.decode_delivered,
                    "err_replies": self.decode_err_replies,
                    "transport_failed": self.decode_transport_failed,
                    "migrated_out": self.migrated_out,
                    "tokens": self.decode_tokens,
                },
                "generations": gens,
            }

    # -- chaos hooks -------------------------------------------------------
    def hang_once(self, hang_s: float) -> None:
        """`replica_hang`/`proc_hang`: the worker's next dispatch
        attempt sleeps `hang_s` (one-shot, armed over the wire)."""
        try:
            self._send(CTRL, 0, json.dumps(
                {"op": "hang_once", "s": float(hang_s)}
            ).encode("utf-8"))
        except ServeClosedError:
            pass

    def freeze_health(self, for_s: float) -> None:
        """`stale_health`: freeze the health surface on the current
        snapshot — its timestamp stops advancing, so the router must
        eject once `health_max_age_s` passes."""
        self._frozen_snap = self.health()
        self._frozen_until = time.perf_counter() + float(for_s)

    def stall_pipe(self, stall_s: float) -> None:
        """`pipe_stall`: the parent's NEXT frame write sleeps
        `stall_s` while holding the pipe — admission ACKs back up
        behind it and the IPC deadline machinery must absorb it."""
        self._stall_s = float(stall_s)

    def tear_next_frame(self) -> None:
        """`torn_frame`: the worker corrupts its next reply frame.
        The parent's CRC check must refuse it, fail in-flight futures
        loudly, and kill/respawn the worker — a truncated reply can
        never be delivered as data."""
        try:
            self._send(CTRL, 0, json.dumps(
                {"op": "torn_frame"}).encode("utf-8"))
        except ServeClosedError:
            pass

    def net_fault(self, kind: str, **kw) -> None:
        """Route a `net_*` chaos kind into the replica's armed
        `ChaosProxy` (no-op without one — the router's chaos layer
        probes via getattr, same as the other proc-only kinds):
        partition/half_open are timed both/one-direction stalls, the
        rest arm the proxy's next-frame one-shots."""
        px = self._proxy
        if px is None:
            return
        if kind == "net_partition":
            px.partition(float(kw.get("t_s", 0.4)))
        elif kind == "net_half_open":
            px.half_open(float(kw.get("t_s", 0.3)),
                         direction=kw.get("direction", "u2c"))
        elif kind == "net_delay":
            px.delay_next(float(kw.get("ms", 5.0)))
        elif kind == "net_reorder":
            px.reorder_next()
        elif kind == "net_dup":
            px.duplicate_next()
        elif kind == "net_drip":
            px.drip_next()

    # -- reader thread -----------------------------------------------------
    def _read_loop(self, sock: socket.socket, reader: FrameReader,
                   gen: int) -> None:
        while True:
            if self._sock is not sock:
                return  # superseded by a restart
            try:
                chunk = sock.recv(1 << 16)
            except socket.timeout:
                self._sweep_deadlines()
                p = self._proc
                dead = (p.poll() is not None if p is not None
                        else not self._tcp)
                if dead and reader.pending_bytes() == 0:
                    self._on_dead(gen, sock)
                    return
                continue
            except OSError:
                self._on_conn_lost(gen, sock)
                return
            if not chunk:
                self._on_conn_lost(gen, sock)
                return
            try:
                frames = reader.feed(chunk)
            except FrameCorruptError as e:
                self._on_corrupt(gen, sock, e)
                return
            for ftype, rid, payload in frames:
                try:
                    self._handle_frame(ftype, rid, payload, gen)
                except FrameCorruptError as e:
                    self._on_corrupt(gen, sock, e)
                    return
                except Exception:
                    pass  # one bad record must not kill the reader
            self._sweep_deadlines()

    def _handle_frame(self, ftype: int, rid: int, payload: bytes,
                      gen: int) -> None:
        g = self._gens[gen]
        if ftype == ACK:
            t_recv = time.perf_counter()
            with self._plock:
                ent = self._pending.get(rid)
                if ent is None:
                    return
                ent.acked = True
                g.admitted += 1
            if len(payload) == 8 and ent.t_send is not None:
                # traced ACK: the worker stamped its perf_counter —
                # midpoint-minus-stamp is the clock offset, and the
                # smallest-RTT handshake gives the tightest estimate
                (t_w,) = struct.unpack(">d", payload)
                g.clock.add(ent.t_send, t_recv, t_w)
                g.clock_rtt_s = g.clock.rtt_s()
                g.clock_offset_us = g.clock.offset_us()
                if ent.trace is not None:
                    # the IPC transit leg of this request's timeline
                    trace_mod.record_span(
                        "ipc", ent.t_send, t_recv, trace=ent.trace,
                        replica=self.name)
                    slo_mod.observe("ipc", t_recv - ent.t_send)
            ent.ack_ev.set()
        elif ftype == REP:
            with self._plock:
                ent = self._pending.pop(rid, None)
                if ent is not None:
                    g.frames += 1
            if ent is None:
                return
            try:
                flags = payload[0]
                late = bool(flags & 1)
                value, off = decode_tree_prefix(payload, 1)
                if flags & 2:
                    # piggybacked worker spans (bounded per frame)
                    (sn,) = struct.unpack_from(">I", payload, off)
                    off += 4
                    self._note_shipped(gen, json.loads(
                        payload[off:off + sn].decode("utf-8")))
                    off += sn
                if off != len(payload):
                    raise FrameCorruptError(
                        f"{len(payload) - off} trailing bytes after "
                        "the reply tree: codec desync")
            except Exception as e:
                # CRC passed but the payload does not decode (codec
                # desync / version skew): the entry is already popped,
                # so fail ITS future here — a stranded caller would
                # hang past every failover — then treat the stream as
                # corrupt like any other framing damage.
                if ent.reply._fail(ProcTransportError(
                        f"replica {self.name}: reply frame {rid} "
                        f"failed to decode ({e!r})")):
                    self.transport_failed += 1
                    note_remote_terminal("failed")
                raise FrameCorruptError(
                    f"undecodable REP payload for {rid}: {e!r}")
            if late:
                ent.reply.deadline_exceeded = True
            if ent.reply._deliver(value):
                if ent.decode:
                    self.decode_delivered += 1
                    note_remote_decode_terminal("completed")
                else:
                    self.delivered += 1
                    note_remote_terminal("replies", late=late)
        elif ftype == TOK:
            with self._plock:
                ent = self._pending.get(rid)
            if ent is None or not ent.decode:
                return  # late token for a swept/unknown session:
                # dropped — never appended to a terminal stream
            toks = np.frombuffer(payload, ">i4")
            for t in toks:
                ent.reply._push_token(int(t))
            self.decode_tokens += len(toks)
            note_remote_decode_tokens(len(toks))
        elif ftype == MIGRATE:
            ckpt = decode_tree(payload)
            with self._plock:
                ent = self._pending.pop(rid, None)
                if ent is not None:
                    g.migrated += 1
            if ent is None:
                return
            # the session LEFT this replica's books without a terminal
            # (the worker already decremented its own `sessions`):
            # mirror the net-out, then hand the checkpoint to whoever
            # holds the reply — the fleet's stream proxy re-places it.
            # Mirror ONLY on the first-write win: a sweep-failed
            # session already booked its terminal, and netting it out
            # here too would break the parent's 4-equation books.
            if ent.reply._fail(ServeMigratedError(
                    f"replica {self.name}: decode session migrated "
                    "off the draining worker "
                    f"({len(np.asarray(ckpt.get('toks', ())).ravel())}"
                    " tokens in the ledger)", ckpt=ckpt)):
                self.migrated_out += 1
                note_remote_decode_export()
        elif ftype == ERR:
            d = json.loads(payload.decode("utf-8"))
            err = decode_error(d)
            with self._plock:
                ent = self._pending.pop(rid, None)
                if ent is None:
                    return
                if not ent.acked:
                    # admission refusal: record the verdict and take
                    # the one-terminal claim under the SAME lock the
                    # submit()-timeout path uses — both firing would
                    # mirror two terminals for one request
                    g.ack_errs += 1
                    ent.ack_err = err
                    claim = ent.take_claim()
            if not ent.acked:
                if claim:
                    kind = d.get("kind", "dispatch")
                    if ent.decode:
                        note_remote_decode_terminal(
                            _DECODE_ERR_TERMINAL.get(kind, "failed"))
                    else:
                        note_remote_terminal({
                            "overload": "shed",
                            "queue_full": "dropped",
                            "overflow": "overflowed",
                        }.get(kind, "failed"))
                if isinstance(err, ServeClosedError):
                    # the parent mirrored requests+<terminal> for
                    # this refusal: the router must count it
                    # `refused` so the routing equation stays exact
                    err.counted = True
                ent.ack_ev.set()
                return
            with self._plock:
                g.frames += 1
            if ent.reply._fail(err):
                if ent.decode:
                    self.decode_err_replies += 1
                    note_remote_decode_terminal(
                        _DECODE_ERR_TERMINAL.get(
                            d.get("kind", "dispatch"), "failed"))
                else:
                    self.err_replies += 1
                    note_remote_terminal(_ERR_TERMINAL.get(
                        d.get("kind", "dispatch"), "failed"))
        elif ftype == HB:
            t_rx = time.perf_counter()
            hb = json.loads(payload.decode("utf-8"))
            spans = hb.pop("spans", None)
            if spans:
                self._note_shipped(gen, spans)
            s_payload = hb.pop("slo", None)
            if s_payload is not None:
                # ISSUE 20: cumulative sketch payload, last-writer-
                # wins keyed by (replica, generation) — a stale
                # generation's heartbeat can never clobber the
                # respawn's fresh sketches
                slo_mod.ingest_wire(self.name, s_payload, gen=gen)
            clock = hb.get("clock")
            if clock and g.clock_wall_us is None:
                # wall-clock fallback offset (same host, so the wall
                # clocks agree): parent-mono-at-send ~= t_rx adjusted
                # by the wall delta; only the ACK handshake refines it
                g.clock_wall_us = ((clock["wall"] - time.time() + t_rx)
                                   - clock["mono"]) * 1e6
            self._hb = hb
            self._hb_rx = t_rx
            self.hb_received += 1
        elif ftype == CTRL_OK:
            with self._plock:
                waiter = self._ctrl_pending.get(rid)
            if waiter is not None:
                waiter["box"]["result"] = json.loads(
                    payload.decode("utf-8"))
                waiter["ev"].set()
        elif ftype == BYE:
            bye = json.loads(payload.decode("utf-8"))
            spans = bye.pop("spans", None)
            if spans:
                self._note_shipped(gen, spans)
            s_payload = bye.pop("slo", None)
            if s_payload is not None:
                # final cumulative state at clean shutdown — nothing
                # sampled after the last heartbeat is lost
                slo_mod.ingest_wire(self.name, s_payload, gen=gen)
            g.handshake = bye
            g.clean = True

    def _note_shipped(self, gen: int, spans) -> None:
        """Buffer shipped worker spans (bounded — overflow drops the
        OLDEST, counted `spans_dropped`, never an unbounded list)."""
        with self._plock:
            for rec in spans:
                if not isinstance(rec, dict) or "name" not in rec:
                    continue
                if len(self._shipped) >= _MAX_SHIPPED:
                    self._shipped.popleft()
                    self.spans_dropped += 1
                self._shipped.append((gen, rec))
                self.spans_received += 1

    def trace_source(self):
        """Span sources for `trace.merge_chrome_traces`: one per
        worker GENERATION that shipped spans, each carrying that
        generation's pid and estimated clock offset — a respawned
        worker is a new process with a new `perf_counter` origin, so
        its spans need their own shift."""
        with self._plock:
            by_gen: Dict[int, List[Dict]] = {}
            for gnum, rec in self._shipped:
                by_gen.setdefault(gnum, []).append(rec)
        out = []
        for gnum, recs in sorted(by_gen.items()):
            g = self._gens.get(gnum)
            out.append({
                "records": recs,
                "pid": None if g is None else g.pid,
                "offset_us": 0.0 if g is None else g.offset_us(),
                "replica": self.name,
                "gen": gnum,
            })
        return out

    def _sweep_deadlines(self) -> None:
        now = time.perf_counter()
        victims: List[_Pending] = []
        with self._plock:
            for ent in self._pending.values():
                if (ent.acked and not ent.sweep_failed
                        and ent.ipc_abs is not None
                        and now >= ent.ipc_abs):
                    ent.sweep_failed = True
                    victims.append(ent)
        for ent in victims:
            self.ipc_timeouts += 1
            if ent.reply._fail(ProcTransportError(
                    f"replica {self.name}: no reply within the IPC "
                    f"deadline ({self.ipc_deadline_s * 1e3:.0f} ms "
                    "past the request deadline) — worker hung or "
                    "pipe stalled")):
                if ent.decode:
                    self.decode_transport_failed += 1
                    note_remote_decode_terminal("failed")
                else:
                    self.transport_failed += 1
                    note_remote_terminal("failed")
            # the entry STAYS pending: if the worker is merely slow
            # its frame still arrives (dropped, but counted), and if
            # the worker dies the death sweep moves it to `swept` —
            # either way the generation ledger closes exactly.

    def _fail_all_pending(self, err: BaseException) -> None:
        with self._plock:
            victims = list(self._pending.items())
            self._pending.clear()
            ctrl = list(self._ctrl_pending.values())
            self._ctrl_pending.clear()
        for rid, ent in victims:
            with self._plock:
                g = self._gens.get(ent.gen)
                if g is not None and ent.acked:
                    g.swept += 1
                claim = (not ent.acked) and ent.take_claim()
            won = ent.reply._fail(err)
            if not ent.acked:
                # submit() is still waiting on the ACK: wake it with
                # the terminal error so the caller is never stranded.
                # counted=True: the failed bucket below keeps the
                # engine equation exact, so the router must book the
                # refusal too.
                ent.ack_err = ServeClosedError(str(err))
                ent.ack_err.counted = True
                ent.ack_ev.set()
                if claim:
                    # never admitted => never in `sent`: mirror the
                    # terminal but keep it out of transport_failed
                    # (the parent-terminals equation is over admitted
                    # requests only)
                    if ent.decode:
                        note_remote_decode_terminal("failed")
                    else:
                        note_remote_terminal("failed")
                continue
            if won:
                if ent.decode:
                    # a SIGKILLed worker's live sessions fail LOUDLY
                    # here; the fleet's stream proxy re-prefills from
                    # its delivered-token ledger (replay — migration
                    # is only the fast path)
                    self.decode_transport_failed += 1
                    note_remote_decode_terminal("failed")
                else:
                    self.transport_failed += 1
                    note_remote_terminal("failed")
        for waiter in ctrl:
            waiter["ev"].set()

    def _on_conn_lost(self, gen: int, sock: socket.socket) -> None:
        """Socket EOF/error. Spawn mode: the connection IS the process
        — child death. TCP modes: the connection is only the NETWORK;
        unless the (local) worker is observably dead or the stop path
        asked for this, the generation gets its bounded reconnect
        window: in-flight requests fail over NOW (PR 11 machinery —
        never hang), health reads unhealthy so the router ejects, and
        a reconnect HELLO carrying the current fence resumes the same
        generation. Window expiry (checked by the health/liveness
        probes) declares the generation dead."""
        with self._plock:
            if sock in self._superseded:
                # a same-fence reconnect already replaced this
                # connection — its loss is old news, not a new window
                self._superseded.discard(sock)
                return
        if not self._tcp:
            self._on_dead(gen, sock)
            return
        g = self._gens.get(gen)
        p = self._proc
        if (self._draining or self.killed
                or (g is not None and g.clean)
                or (p is not None and p.poll() is not None)):
            self._on_dead(gen, sock)
            return
        fresh = False
        with self._plock:
            if self._sock is sock:
                self._sock = None
            if not self._reconnecting:
                self._reconnecting = True
                fresh = True
            self._reconnect_deadline = (time.perf_counter()
                                        + self.reconnect_window_s)
        try:
            sock.close()
        except OSError:
            pass
        if fresh:
            self.reconnect_windows += 1
        self._fail_all_pending(ProcTransportError(
            f"worker {self.name} (gen {gen}) connection lost; "
            "in-flight requests fail over while the worker gets a "
            f"{self.reconnect_window_s:g}s reconnect window"))
        if self._mode == "connect":
            t = threading.Thread(target=self._redial_loop,
                                 name=f"singa_tpu-redial-{self.name}",
                                 daemon=True)
            t.start()

    def _redial_loop(self) -> None:
        """`connect` mode owns re-establishment from the parent side:
        seeded-backoff redials of the worker's listen address until
        the handshake resumes the generation or the window expires."""
        from . import resilience

        attempt = 0
        while True:
            with self._plock:
                if (not self._reconnecting or self._sock is not None
                        or self.killed):
                    return
                deadline = self._reconnect_deadline
            attempt += 1
            delay = resilience.backoff_delay_s(
                attempt, 0.05, seed=hash(self.name) & 0x7FFFFFFF,
                salt="redial")
            if time.perf_counter() + delay >= deadline:
                time.sleep(max(0.0, deadline - time.perf_counter()))
                self._reconnect_active()  # flips killed on expiry
                return
            time.sleep(delay)
            try:
                conn = socket.create_connection(
                    (self._host, self._port), timeout=5.0)
            except OSError:
                continue
            try:
                self._tcp_handshake(conn)
                return
            except Exception:
                try:
                    conn.close()
                except OSError:
                    pass

    def _on_dead(self, gen: int, sock: socket.socket) -> None:
        p = self._proc
        code = None
        if p is not None:
            try:
                # EOF usually beats the kernel's exit bookkeeping by
                # a hair: wait for the real exit code — the child
                # exit code IS the crash-detection evidence
                code = p.wait(5.0)
            except subprocess.TimeoutExpired:
                code = p.poll()
        g = self._gens.get(gen)
        if g is not None and g.exit_code is None:
            g.exit_code = code
        if self._sock is sock:
            self._sock = None
            try:
                sock.close()
            except OSError:
                pass
        if not self._draining and not (g is not None and g.clean):
            self.killed = True
        self._fail_all_pending(ProcTransportError(
            f"worker {self.name} (gen {gen}) died with the request "
            f"in flight (exit code {code})"))

    def _on_corrupt(self, gen: int, sock: socket.socket,
                    e: FrameCorruptError) -> None:
        """Fail closed on stream corruption: every in-flight future
        fails LOUDLY — a corrupt stream cannot be resynced by
        guessing. Spawn mode kills the worker for respawn (the
        connection is the process). TCP modes tear down only the
        CONNECTION: corruption there indicts the network (duplicated,
        reordered, torn frames), not the process, so the worker gets
        its reconnect window and a FRESH stream (sequence numbers
        restart) — replay/gap damage is counted per taxonomy either
        way and never delivered as data."""
        self.torn_frames_detected += 1
        if isinstance(e, FrameReplayError):
            self.replay_frames_detected += 1
        elif isinstance(e, FrameGapError):
            self.gap_frames_detected += 1
        import sys as _sys

        print(f"singa_tpu: replica {self.name} frame stream corrupt "
              f"({e}); failing in-flight requests and "
              + ("dropping the connection for reconnect"
                 if self._tcp else "killing the worker for respawn"),
              file=_sys.stderr)
        if self._tcp and not self._draining:
            self._on_conn_lost(gen, sock)
            return
        self.killed = True
        self.sigkill()
        self._on_dead(gen, sock)
