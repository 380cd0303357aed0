"""Deterministic network-fault proxy (ISSUE 18): unit tests for
`singa_tpu.netchaos.ChaosProxy` against a plain loopback upstream —
no workers, no engine, ephemeral ports only.

Acceptance pins here:
  - passthrough is byte-exact: with no faults armed, a seq-checked
    `FrameReader` on the far side decodes the identical frames;
  - `duplicate_next` produces a frame the receiver REFUSES as
    `FrameReplayError` (typed, counted, never delivered as data);
  - `reorder_next` produces a sequence gap the receiver refuses as
    `FrameGapError`;
  - `partition` stalls delivery for its full duration and then HEALS
    with every buffered byte intact — a partition is not corruption;
  - `drip_next` (1-byte writes) delivers the frame intact — the
    reader-compaction worst case is a latency story, not a loss one;
  - a non-frame byte stream drops to raw passthrough: the proxy
    never invents bytes and never eats them;
  - probabilistic draws are seed-keyed and deterministic.
"""
import socket
import time

import pytest

from singa_tpu import fleet_proc, netchaos
from singa_tpu.fleet_proc import FrameGapError, FrameReplayError


def _upstream():
    ls = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    ls.bind(("127.0.0.1", 0))
    ls.listen(1)
    ls.settimeout(5.0)
    return ls


def _pair(px, ls):
    """Client socket dialing the proxy + the upstream's accepted end."""
    c = socket.create_connection(px.addr, timeout=5.0)
    s, _ = ls.accept()
    s.settimeout(5.0)
    return c, s


def _frames(n, start_seq=0):
    return [fleet_proc.encode_frame(fleet_proc.HB, i, b"p%d" % i,
                                    seq=start_seq + i)
            for i in range(n)]


def _recv_frames(sock, reader, want_n, timeout_s=5.0):
    out = []
    deadline = time.perf_counter() + timeout_s
    sock.settimeout(0.1)
    while len(out) < want_n and time.perf_counter() < deadline:
        try:
            chunk = sock.recv(1 << 16)
        except socket.timeout:
            continue
        if not chunk:
            break
        out.extend(reader.feed(chunk))
    return out


def _counted(px, key, timeout_s=5.0):
    """The proxy's counter `key` once it is non-zero. The pump thread
    counts a fault AFTER it has shipped the frames, so a reader that
    already holds them may look before the count has landed: wait on
    the count, not on the bytes."""
    deadline = time.perf_counter() + timeout_s
    while not px.snapshot()[key] and time.perf_counter() < deadline:
        time.sleep(0.005)
    return px.snapshot()[key]


@pytest.fixture()
def loop():
    ls = _upstream()
    px = netchaos.ChaosProxy(upstream=ls.getsockname()).start()
    yield px, ls
    px.stop()
    ls.close()


def test_passthrough_is_frame_exact(loop):
    px, ls = loop
    c, s = _pair(px, ls)
    frames = _frames(5)
    for f in frames:
        c.sendall(f)
    rd = fleet_proc.FrameReader(check_seq=True)
    got = _recv_frames(s, rd, 5)
    assert [(t, rid, p) for t, rid, p in got] == \
        [(fleet_proc.HB, i, b"p%d" % i) for i in range(5)]
    snap = px.snapshot()
    assert snap["frames"] == 5 and snap["conns"] == 1
    assert snap["dups"] == snap["reorders"] == snap["drips"] == 0
    c.close()
    s.close()


def test_duplicate_is_refused_as_replay_never_data(loop):
    px, ls = loop
    c, s = _pair(px, ls)
    px.duplicate_next(direction="c2u")
    for f in _frames(2):
        c.sendall(f)
    rd = fleet_proc.FrameReader(check_seq=True)
    got, err = [], None
    deadline = time.perf_counter() + 5.0
    s.settimeout(0.1)
    while err is None and time.perf_counter() < deadline:
        try:
            chunk = s.recv(1 << 16)
        except socket.timeout:
            continue
        if not chunk:
            break
        try:
            got.extend(rd.feed(chunk))
        except FrameReplayError as e:
            err = e
    assert err is not None, "duplicated frame was never detected"
    # nothing PAST the replay was ever delivered as data (frames
    # decoded in the same chunk before the verdict are torn down
    # with the connection — the transport resends them by rid)
    assert [rid for _, rid, _ in got] in ([], [0])
    assert _counted(px, "dups") == 1
    c.close()
    s.close()


def test_reorder_is_refused_as_gap(loop):
    px, ls = loop
    c, s = _pair(px, ls)
    px.reorder_next(direction="c2u")
    for f in _frames(2):
        c.sendall(f)
    rd = fleet_proc.FrameReader(check_seq=True)
    deadline = time.perf_counter() + 5.0
    s.settimeout(0.1)
    err = None
    while err is None and time.perf_counter() < deadline:
        try:
            chunk = s.recv(1 << 16)
        except socket.timeout:
            continue
        if not chunk:
            break
        try:
            rd.feed(chunk)
        except FrameGapError as e:
            err = e
    assert err is not None, "reordered frames were never detected"
    assert px.snapshot()["reorders"] == 1
    c.close()
    s.close()


def test_partition_stalls_then_heals_intact(loop):
    px, ls = loop
    c, s = _pair(px, ls)
    # prove liveness first so the stall below is the proxy's doing
    c.sendall(_frames(1)[0])
    rd = fleet_proc.FrameReader(check_seq=True)
    assert len(_recv_frames(s, rd, 1)) == 1
    px.partition(0.4)
    t0 = time.perf_counter()
    c.sendall(_frames(1, start_seq=1)[0])
    got = _recv_frames(s, rd, 1, timeout_s=5.0)
    waited = time.perf_counter() - t0
    assert len(got) == 1 and got[0][2] == b"p0"
    assert waited >= 0.3, f"partition healed too early ({waited:.3f}s)"
    assert px.snapshot()["partitions"] == 1
    c.close()
    s.close()


def test_drip_delivers_intact(loop):
    px, ls = loop
    c, s = _pair(px, ls)
    px.drip_next(direction="c2u")
    payload = bytes(range(256)) * 4
    c.sendall(fleet_proc.encode_frame(fleet_proc.REP, 9, payload))
    rd = fleet_proc.FrameReader(check_seq=True)
    got = _recv_frames(s, rd, 1)
    assert got == [(fleet_proc.REP, 9, payload)]
    assert _counted(px, "drips") == 1
    c.close()
    s.close()


def test_non_frame_stream_is_raw_passthrough(loop):
    px, ls = loop
    c, s = _pair(px, ls)
    blob = b"NOT-A-FRAME " * 10  # no SF magic, > header length
    c.sendall(blob)
    got = bytearray()
    deadline = time.perf_counter() + 5.0
    s.settimeout(0.1)
    while len(got) < len(blob) and time.perf_counter() < deadline:
        try:
            got += s.recv(1 << 16)
        except socket.timeout:
            continue
    assert bytes(got) == blob
    assert _counted(px, "raw_chunks") >= 1
    c.close()
    s.close()


def test_draws_are_seed_keyed_and_deterministic():
    a = netchaos._u01(7, 0, "c2u", "dup", 3)
    assert a == netchaos._u01(7, 0, "c2u", "dup", 3)
    assert 0.0 <= a < 1.0
    # any keyed coordinate changes the draw
    assert a != netchaos._u01(8, 0, "c2u", "dup", 3)
    assert a != netchaos._u01(7, 1, "c2u", "dup", 3)
    assert a != netchaos._u01(7, 0, "u2c", "dup", 3)
    assert a != netchaos._u01(7, 0, "c2u", "delay", 3)
    assert a != netchaos._u01(7, 0, "c2u", "dup", 4)


def test_probabilistic_dup_fires_at_rate():
    # the draw behind `dup_prob`, at its rate: over n frame ordinals of
    # one seeded connection it fires n*p times give or take 5 sigma
    # (the same count every run: the draw is a pure function)
    n, p = 4000, 0.1
    fired = sum(netchaos._u01(3, 0, "c2u", "dup", i) < p
                for i in range(n))
    assert abs(fired - n * p) <= 5 * (n * p * (1 - p)) ** 0.5, fired
    ls = _upstream()
    px = netchaos.ChaosProxy(upstream=ls.getsockname(),
                             seed=3, dup_prob=1.0).start()
    try:
        c, s = _pair(px, ls)
        c.sendall(_frames(1)[0])
        # dup_prob=1.0: the single frame is shipped twice
        rd = fleet_proc.FrameReader()  # seq-blind: count raw copies
        got = _recv_frames(s, rd, 2)
        assert [rid for _, rid, _ in got] == [0, 0]
        assert _counted(px, "dups") == 1
        c.close()
        s.close()
    finally:
        px.stop()
        ls.close()
