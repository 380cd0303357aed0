"""Device time of a training step by the program's own scopes, and the
step's host phases, from a traced run's .xplane.pb.

A trace names a device operation by its whole HLO instruction and
carries no `jax.named_scope`; the program's optimised HLO text carries
every instruction's scope. So the program does the join
(`singa_tpu/hlo_profile.py`): `step_programs()` hands over the step's
text, compiled on demand, `scope_map()` makes instruction ->
scope of it, and `scope_times()` reduces one chip's `(name, t0, t1)`
events, inside the events of that module on the line "XLA Modules", to
device self-time by scope with the part it could not place. This
module loads the file, asks, takes the mean over the chips and divides
by the traced steps. Which scopes a metric sums is its reader's: a
regular expression over the scope, in the reader's file.

The step's host phases (`singa:step.call`, `.place`, `.enqueue`,
`.bind`: `trace.phase`, annotations with the program's tracer off) are
in the plane "/host:CPU" on the same clock.

A program without the scopes, the registry or the phases (the parent
of the PR that added them) leaves every reader with nothing to read:
None, and the metric is left out.
"""
import re

from . import moe_trace, numbers, profiler, xplane

PREFIX = "singa:"
CALL, PLACE = "step.call", "step.place"


def step_maps():
    """`scope_map` of each of the process's step programs, and the
    program's `scope_times`; None where the program has neither."""
    try:
        from singa_tpu import hlo_profile
    except ImportError:
        return None
    if not hasattr(hlo_profile, "step_programs"):
        return None
    maps = [hlo_profile.scope_map(text)
            for _, text in hlo_profile.step_programs()]
    maps = [m for m in maps if m["instructions"]]
    return (maps, hlo_profile.scope_times) if maps else None


def reduce(devices, modules, maps, scope_times, w0, w1):
    """Mean over the chips of `scope_times` over each chip's events
    that start inside the window, once a map: each takes the events
    inside its own module's events on the chip's "XLA Modules" line
    (a trace without that line gives all to the first map). All in ns.

    {"rows": {(scope, dir): ns}, "unplaced": {why: ns},
    "unplaced_by_opcode": {opcode: ns}, "total": ns of the maps'
    modules, "elsewhere": ns of other programs' operations, "matched",
    "unmatched": events, "scoped", "unscoped": the maps' own coverage}."""
    out = {"rows": {}, "unplaced": {}, "unplaced_by_opcode": {},
           "total": 0.0, "elsewhere": 0.0, "matched": 0, "unmatched": 0,
           "scoped": sum(m["scoped"] for m in maps),
           "unscoped": sum(m["unscoped"] for m in maps)}
    k = max(1, len(devices))

    def add(into, key, ns):
        into[key] = into.get(key, 0.0) + ns / k

    for chip, ops in devices.items():
        ops = [o for o in ops if w0 <= o[1] < w1]
        mods = modules.get(chip)
        for n, smap in enumerate(maps if mods is not None else maps[:1]):
            red = scope_times(ops, smap, mods)
            if n == 0:      # every operation's self-time, whoever's
                out["elsewhere"] += (red["total"] + red["elsewhere"]) / k
            for row in red["rows"]:
                add(out["rows"], (row["scope"], row["dir"]), row["time"])
            for why, ns in red["unplaced"].items():
                add(out["unplaced"], why, ns)
            for opcode, ns in red["unplaced_by_opcode"].items():
                add(out["unplaced_by_opcode"], opcode, ns)
            out["total"] += red["total"] / k
            out["elsewhere"] -= red["total"] / k
            out["matched"] += red["matched"]
            out["unmatched"] += red["unmatched"]
    return out


def under(red, pattern):
    """ns of the reduction's rows whose scope `pattern` matches."""
    rx = re.compile(pattern)
    return sum(ns for (scope, _), ns in red["rows"].items()
               if rx.search(scope))


def scoped_pct(red):
    """Share of all the window's device busy time that is under a
    scope of the program's."""
    busy = red["total"] + red["elsewhere"]
    return 100.0 * sum(red["rows"].values()) / busy if busy else None


def idle_under_pct(devices, spans, name, w0, w1):
    """Share of the window in which a chip runs nothing while the host
    is inside a span `name`; mean over the chips, by intersection."""
    inside = xplane.merge(xplane.clip(
        [(t0, t1) for n, t0, t1 in spans if n == name], w0, w1))
    shares = []
    for ops in devices.values():
        busy = xplane.merge(xplane.clip([(o[1], o[2]) for o in ops], w0, w1))
        idle = xplane.subtract([[w0, w1]], busy)
        shares.append((xplane.total(idle)
                       - xplane.total(xplane.subtract(idle, inside)))
                      / (w1 - w0))
    return 100.0 * sum(shares) / len(shares) if shares else None


def describe(red, steps):
    """One line: events matched, the heaviest scopes, what is left."""
    n = max(1, steps)
    top = sorted(red["rows"].items(), key=lambda kv: -kv[1])[:10]
    left = ", ".join(f"{op} {ns / n / 1e6:.3f}" for op, ns in
                     list(red["unplaced_by_opcode"].items())[:6])
    return (
        f"{red['matched']} events matched, {red['unmatched']} not; map "
        f"{red['scoped']} instructions scoped, {red['unscoped']} not; "
        f"device ms a step over {steps} steps: step program "
        f"{red['total'] / n / 1e6:.3f}, other programs "
        f"{red['elsewhere'] / n / 1e6:.3f}; heaviest scopes: "
        + ", ".join(f"{scope} {d or '-'} {ns / n / 1e6:.3f}"
                    for (scope, d), ns in top)
        + "; unplaced: "
        + ", ".join(f"{why} {ns / n / 1e6:.3f}"
                    for why, ns in red["unplaced"].items())
        + (f" (by opcode: {left})" if left else ""))


def describe_phases(devices, spans, bench, w0, w1):
    """One line: the four phases' medians beside the benchmark's own
    annotation around the same call (`bench:model(x, y)`, same trace,
    same clock), and the device's idle share of the window by the
    phase the host was in meanwhile."""
    def p50(events, name):
        durs = [t1 - t0 for n, t0, t1 in events
                if n == name and w0 <= t0 and t1 <= w1]
        return numbers.median(durs) / 1e6 if durs else float("nan")

    leaves = ("step.place", "step.enqueue", "step.bind")
    idle = {n: idle_under_pct(devices, spans, n, w0, w1)
            for n in (CALL,) + leaves}
    busy = xplane.busy_by_chip(
        xplane.Trace(devices=devices), w0, w1)
    total = 100.0 * (1 - sum(busy.values()) / len(busy) / ((w1 - w0) / 1e9))
    rest = idle[CALL] - sum(idle[n] for n in leaves)
    return (
        "median ms in the traced sub-window: "
        + ", ".join(f"{n} {p50(spans, n):.3f}" for n in (CALL,) + leaves)
        + f", the benchmark's model(x, y) {p50(bench, 'bench:model(x, y)'):.3f}"
        + f"; device idle {total:.2f} % of the window = "
        + " + ".join(f"{n} {idle[n]:.2f}" for n in leaves)
        + f" + the call's own lines {rest:.2f} + outside the call "
        f"{total - idle[CALL]:.2f}")


def load(path):
    """A trace file's device operations, its executed programs and the
    program's host spans (prefix off), by chip and by start."""
    tr = xplane.load(path, host_prefix=PREFIX)
    return (tr.devices, moe_trace.modules(path),
            [(name[len(PREFIX):], t0, t1) for name, t0, t1 in tr.host])


def _of_run(run):
    if run.device_trace is None or "traced_steps" not in run.samples:
        return None
    try:
        path = xplane.newest_xplane(profiler.trace_dir(run.cell["name"]))
    except FileNotFoundError:
        return None
    devices, modules, spans = load(path)
    out = {"devices": devices, "spans": spans,
           "steps": run.samples["traced_steps"], "scopes": None}
    asked = step_maps()
    if asked is not None:
        maps, scope_times = asked
        out["scopes"] = reduce(devices, modules, maps, scope_times,
                               *run.trace_window_ns)
    if any(s[0] == CALL for s in spans):
        run.notes["step_phases"] = describe_phases(
            devices, spans, run.device_trace.host, *run.trace_window_ns)
    return out


def of_run(run):
    """What the readers read, computed once per run and kept on it:
    {"devices", "spans", "steps", "scopes": `reduce`'s result or None};
    None without a device trace. Leaves the line among the run's
    notes."""
    if not hasattr(run, "scope_trace"):
        run.scope_trace = _of_run(run)
        got = run.scope_trace
        if got is not None and got["scopes"] is not None:
            run.notes["step_scopes"] = describe(got["scopes"], got["steps"])
    return run.scope_trace


def step_ms(run, pattern):
    """Device ms a traced step under the scopes `pattern` matches; None
    without a trace, a map, or anything under them."""
    got = of_run(run)
    if got is None or got["scopes"] is None or not got["steps"]:
        return None
    ns = under(got["scopes"], pattern)
    return ns / got["steps"] / 1e6 if ns else None


def phase_ms_p50(run, name):
    """Median ms of the program's phase `name` inside the traced
    window; None where the trace holds none."""
    got = of_run(run)
    if got is None:
        return None
    w0, w1 = run.trace_window_ns
    durs = [t1 - t0 for n, t0, t1 in got["spans"]
            if n == name and w0 <= t0 and t1 <= w1]
    return numbers.median(durs) / 1e6 if durs else None
