"""The program's own spans on the device trace's clock.

While the program's tracer is on, every `trace.span(name)` is also a
`jax.profiler.TraceAnnotation("singa:" + name)`, so a traced run's
.xplane.pb holds the decode dispatcher's cycle in "/host:CPU" beside
the device's operations, on one clock. This module splits the device's
idle time by what the dispatcher was doing meanwhile, and times the
way back from the device's last operation to the host. A program that
has no such spans (the parent of the PR that added them) leaves every
reader here with nothing to read: None, and the metric is left out.

All arithmetic is over plain (name, t0_ns, t1_ns) tuples, as in
`xplane`, so the tests drive it without a trace.
"""
import bisect

from . import numbers, profiler, xplane

PREFIX = "singa:"
# the dispatcher's leaf spans (`singa_tpu/serve.py`, decode tier), each
# under the bucket its idle time is counted in
LEAVES = {
    "decode.wait_work": "nowork",
    "decode.admit": "hostwork",
    "decode.prefill.assemble": "hostwork",
    "decode.prefill.dispatch": "dispatch",
    "decode.prefill.readback": "readback",
    "decode.prefill.scatter": "hostwork",
    "decode.step.assemble": "hostwork",
    "decode.step.dispatch": "dispatch",
    "decode.step.readback": "readback",
    "decode.step.scatter": "hostwork",
}
UNATTRIBUTED = "unattributed"
CYCLE = "decode.step.dispatch"      # one fused step or block a cycle
TAIL = "decode.step.readback"


def flatten(spans):
    """Disjoint (name, t0, t1) pieces in time order: at every instant
    the innermost span that covers it, which on one thread is the one
    that started last."""
    out, stack, cur = [], [], None

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][2] <= t:
            name, _, t1 = stack.pop()
            if t1 > cur:
                out.append((name, cur, t1))
                cur = t1

    for span in sorted(spans, key=lambda s: (s[1], -s[2])):
        if span[2] <= span[1]:
            continue
        close_until(span[1])
        if stack and span[1] > cur:
            out.append((stack[-1][0], cur, span[1]))
        stack.append(span)
        cur = span[1]
    close_until(float("inf"))
    return out


def idle_by_span(devices, spans, w0, w1):
    """ns inside the window in which a chip ran no operation, by the
    name of the leaf span the dispatcher was in (UNATTRIBUTED: in
    none); mean over the chips. By intersection: one gap that runs
    through four spans is split among the four."""
    under = {}
    for name, t0, t1 in flatten(s for s in spans if s[0] in LEAVES):
        under.setdefault(name, []).append((t0, t1))
    under = {name: xplane.merge(xplane.clip(ivs, w0, w1))
             for name, ivs in under.items()}
    out = dict.fromkeys(list(LEAVES) + [UNATTRIBUTED], 0.0)
    for ops in devices.values():
        busy = xplane.merge(xplane.clip([(o[1], o[2]) for o in ops], w0, w1))
        idle = xplane.subtract([[w0, w1]], busy)
        idle_ns = left = xplane.total(idle)
        for name, ivs in under.items():
            ns = idle_ns - xplane.total(xplane.subtract(idle, ivs))
            out[name] += ns / len(devices)
            left -= ns
        out[UNATTRIBUTED] += left / len(devices)
    return out


def buckets(by_span):
    """`idle_by_span`'s result summed into the five buckets."""
    out = dict.fromkeys(list(LEAVES.values()) + [UNATTRIBUTED], 0.0)
    for name, ns in by_span.items():
        out[LEAVES.get(name, UNATTRIBUTED)] += ns
    return out


def readback_tails(devices, spans, w0, w1):
    """Per TAIL span inside the window, ns from the later of its own
    start and the end of the last device operation that ended before
    it closed, to its end: how long after the chip had finished the
    host held the result."""
    ends = sorted(o[2] for ops in devices.values() for o in ops)
    out = []
    for name, t0, t1 in spans:
        if name != TAIL or t0 < w0 or t1 > w1:
            continue
        i = bisect.bisect_right(ends, t1)
        out.append(t1 - max(t0, ends[i - 1] if i else t0))
    return out


def reduce(devices, spans, w0, w1):
    """Everything the readers report, from tuples."""
    by_span = idle_by_span(devices, spans, w0, w1)
    tails = readback_tails(devices, spans, w0, w1)
    return {"window_ns": w1 - w0,
            "cycles": sum(1 for name, t0, _ in spans
                          if name == CYCLE and w0 <= t0 < w1),
            "idle_ns_by_span": by_span,
            "idle_pct": {k: 100.0 * v / (w1 - w0)
                         for k, v in buckets(by_span).items()},
            "readback_tail_ms_p50": (numbers.median(tails) / 1e6
                                     if tails else None)}


def describe(red):
    """One line: device idle ms per cycle under each leaf span."""
    n = max(1, red["cycles"])
    parts = [f"{name.removeprefix('decode.')} {ns / n / 1e6:.3f}"
             for name, ns in red["idle_ns_by_span"].items()]
    return (f"device idle ms per cycle, by the dispatcher's span, over "
            f"{red['cycles']} cycles of {red['window_ns'] / n / 1e6:.2f} ms: "
            + ", ".join(parts))


def load(path):
    """A trace file's device operations (`xplane.Trace.devices`) and
    the program's spans, prefix off, by start."""
    tr = xplane.load(path, host_prefix=PREFIX)
    return tr.devices, [(name[len(PREFIX):], t0, t1)
                        for name, t0, t1 in tr.host]


def _of_run(run):
    if run.device_trace is None:
        return None
    try:
        path = xplane.newest_xplane(profiler.trace_dir(run.cell["name"]))
    except FileNotFoundError:
        return None
    _, spans = load(path)
    if not any(s[0] in LEAVES for s in spans):
        return None
    return reduce(run.device_trace.devices, spans, *run.trace_window_ns)


def of_run(run):
    """`reduce` over a traced run's sub-window, computed once per run
    and kept on it; None without a device trace, without its file, or
    without a span of the dispatcher in it. Leaves the per-cycle line
    among the run's notes."""
    if not hasattr(run, "program_trace"):
        run.program_trace = _of_run(run)
        if run.program_trace is not None:
            run.notes["idle_split"] = describe(run.program_trace)
    return run.program_trace


def idle_pct(run, bucket):
    red = of_run(run)
    return None if red is None else red["idle_pct"][bucket]
