"""Reduction from a `jax.profiler` trace (.xplane.pb) to numbers.

Read with `jax.profiler.ProfileData` (nothing but jax). A TPU trace
has one plane per chip, "/device:TPU:<n>", whose line "XLA Ops" holds
one event per executed HLO operation (start, duration, in ns on the
trace's clock); the host's threads are lines of the plane "/host:CPU",
where the benchmark's own `jax.profiler.TraceAnnotation`s appear under
the names it gave them. All arithmetic below is over plain
(name, start_ns, end_ns) tuples, so the tests drive it without a trace.
"""
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"   # copy-start..done and the like, as spans
HOST_PLANE = "/host:CPU"
# HLO collectives; async pairs show as <op>-start / <op>-done
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all")
_OPCODE = re.compile(r"[\]})] ([a-z][a-z\-]*)\(")


def opcode(name):
    """The HLO opcode of an event. The trace names an event by its whole
    instruction, "%psum.7 = bf16[8]{0} all-reduce(bf16[8]{0} %x), ...":
    the opcode stands between the result's shape and the operands, and
    the instruction's own name says nothing reliable (jax calls an
    all-reduce `psum`). A bare name ("all-reduce.3") is its own opcode."""
    head, sep, rest = name.partition(" = ")
    if sep:
        m = _OPCODE.search(rest)
        return m.group(1) if m else ""
    return head.lstrip("%").split(".", 1)[0]


def is_collective(name):
    return opcode(name).startswith(COLLECTIVES)


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)  # chip -> [(name, t0, t1)]
    asyncs: dict = field(default_factory=dict)   # chip -> [(name, t0, t1)]
    host: list = field(default_factory=list)     # [(name, t0, t1)]


def newest_xplane(logdir):
    paths = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def load(path, host_prefix="bench:"):
    """Device operations per chip and the host annotations whose name
    starts with `host_prefix`."""
    from jax.profiler import ProfileData

    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            lines = {OPS_LINE: tr.devices.setdefault(chip, []),
                     ASYNC_LINE: tr.asyncs.setdefault(chip, [])}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                tr.host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(host_prefix))
    for ops in tr.devices.values():
        ops.sort(key=lambda o: o[1])
    tr.host.sort(key=lambda o: o[1])
    return tr


def describe(path, max_lines=60):
    """Planes, lines and a few event names: what to look at by hand
    before trusting a reduction on a new installation."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        out.append(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines[:max_lines]:
            evs = list(line.events)
            names = sorted({e.name for e in evs[:2000]})[:6]
            out.append(f"  line {line.name!r}: {len(evs)} events {names}")
    return "\n".join(out)


# -- interval arithmetic ----------------------------------------------------
def merge(intervals):
    """Union of (t0, t1) intervals as a sorted list of disjoint ones."""
    out = []
    for t0, t1 in sorted(intervals):
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1][1] = t1
        else:
            out.append([t0, t1])
    return out


def total(intervals):
    return sum(t1 - t0 for t0, t1 in intervals)


def clip(intervals, w0, w1):
    return [(max(t0, w0), min(t1, w1)) for t0, t1 in intervals
            if t1 > w0 and t0 < w1]


def subtract(a, b):
    """The part of merged intervals `a` that no interval of merged `b`
    covers."""
    out, j = [], 0
    for t0, t1 in a:
        cur = t0
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < t1:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < t1:
            out.append([cur, t1])
    return out


def window(tr, annotation="bench:window"):
    """The traced window: the benchmark's own annotation where the
    trace has it, else first device event to last."""
    for name, t0, t1 in tr.host:
        if name == annotation:
            return t0, t1
    t0 = min(ops[0][1] for ops in tr.devices.values() if ops)
    t1 = max(max(o[2] for o in ops) for ops in tr.devices.values() if ops)
    return t0, t1


def busy_by_chip(tr, w0, w1):
    """Seconds in which any operation ran, per chip, inside the window:
    the union of its events' intervals."""
    return {chip: total(merge(clip([(o[1], o[2]) for o in ops], w0, w1))) / 1e9
            for chip, ops in tr.devices.items()}


def idle_pct(tr, w0, w1):
    busy = busy_by_chip(tr, w0, w1)
    win = (w1 - w0) / 1e9
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / win)


def collective_exposed_pct(tr, w0, w1):
    """Time in collective operations during which no other operation
    runs on that chip, over the window; mean over chips. None when the
    trace holds no collective. A collective counts whether the trace
    shows it as an operation of its own (or a -start / -done pair) or
    as a span of the asynchronous line."""
    shares, seen = [], False
    for chip, ops in tr.devices.items():
        coll = merge(clip([(o[1], o[2])
                           for o in ops + tr.asyncs.get(chip, [])
                           if is_collective(o[0])], w0, w1))
        rest = merge(clip([(o[1], o[2]) for o in ops
                           if not is_collective(o[0])], w0, w1))
        seen = seen or bool(coll)
        shares.append(total(subtract(coll, rest)) / (w1 - w0))
    return 100.0 * sum(shares) / len(shares) if seen else None


def self_times(ops):
    """Per event, its duration minus what events nested inside it
    cover (a `while` holds its body's operations), so that a sum over
    names counts no nanosecond twice. `ops` sorted by start."""
    out = [0] * len(ops)
    stack = []  # indices of open events
    for i, (_, t0, t1) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= t0:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(t1, ops[stack[-1]][2]) - t0
        out[i] += t1 - t0
        stack.append(i)
    return out


def kernel_seconds(tr, pattern, w0, w1):
    """Summed device time of the events whose name matches `pattern`,
    mean over chips; None when none matches."""
    rx = re.compile(pattern)
    per_chip = [sum(min(o[2], w1) - max(o[1], w0) for o in ops
                    if rx.search(o[0]) and o[2] > w0 and o[1] < w1)
                for ops in tr.devices.values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / 1e9


def short_name(name):
    """The trace names an operation by its whole HLO instruction,
    "%copy.95 = f32[2,64,12,256,64]{...} copy(...), sharding=...":
    keep the instruction's name and its result's shape."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    shape = rest.split("{", 1)[0].split(" ", 1)[0].strip("(,")
    return f"{head.lstrip('%')} {shape}"


def top_ops(tr, w0, w1, n=10):
    """The n operations with most self time, by the names the trace
    gives them (shortened), in seconds (mean over chips)."""
    acc = {}
    for ops in tr.devices.values():
        inside = [o for o in ops if o[2] > w0 and o[1] < w1]
        for (name, _, _), dt in zip(inside, self_times(inside)):
            name = short_name(name)
            acc[name] = acc.get(name, 0) + dt
    k = max(1, len(tr.devices))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def idle_gaps(tr, w0, w1, n=5, unattributed="unannotated"):
    """The n longest intervals in which the busiest-to-watch chip (the
    lowest-numbered) ran nothing, each labelled by the benchmark's
    annotation that covers its midpoint."""
    chip = min(tr.devices)
    busy = merge(clip([(o[1], o[2]) for o in tr.devices[chip]], w0, w1))
    gaps = subtract([[w0, w1]], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g0, g1 in gaps[:n]:
        mid = (g0 + g1) / 2
        # innermost covering annotation: the latest-starting one
        label = unattributed
        for name, t0, t1 in tr.host:
            if name != "bench:window" and t0 <= mid < t1:
                label = name[len("bench:"):] if name.startswith("bench:") \
                    else name
        out.append([label, (g1 - g0) / 1e9])
    return out
