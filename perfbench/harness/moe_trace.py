"""Device time of a routed-expert model's decode steps, by part of the
step, from a traced run's .xplane.pb.

The trace names a device operation by its whole HLO instruction and
carries no `jax.named_scope` (PERF.md, PR 22), so an operation is
placed by the shapes in its text: the held experts' matrices
[E, d, f] / [E, f, d] for the expert products; a 4-d shape that leads
with [slots, key/value heads of the kind] for attention over a
context or a ring. The device plane's line "XLA Modules" holds one
event an executed program, named for the jitted function: the program
names its fused step `slot_step` and a run-ahead block of k steps
`slot_scan_<k>`, so the operations of decode steps are those inside
such an event, and the steps are counted from the names. A program
without those names (the parent of the PR that added them) leaves
every reader with nothing to read: None, and the metric is left out.
"""
import re

from . import profiler, xplane

MODULES_LINE = "XLA Modules"
STEP_MODULE = re.compile(r"^jit_slot_(?:step|scan_(\d+))\(")
CONTAINERS = ("while", "conditional", "call")
PARTS = ("moe_experts", "attn_full", "attn_window")


def modules(path):
    """{chip: [(name, t0_ns, t1_ns)]} of the executed programs."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out[int(m.group(1))] = sorted(
                    ((e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events), key=lambda ev: ev[1])
    return out


def part_patterns(slots, held, d_model, d_ff_expert, kv_heads_full,
                  kv_heads_window):
    """Regex per part of the step over an instruction's text."""
    e, d, f = held, d_model, d_ff_expert
    return {
        "moe_experts": re.compile(
            rf"\[{e},{d},{f}\]|\[{e},{f},{d}\]|\[{e * f},{d}\]"
            rf"|\[{e},{slots},{f}\]"),
        "attn_full": re.compile(rf"\[{slots},{kv_heads_full},\d+,\d+\]"),
        "attn_window": re.compile(rf"\[{slots},{kv_heads_window},\d+,\d+\]"),
    }


def reduce(ops, mods, patterns, w0, w1):
    """From one chip's operations and programs, inside the window:
    decode steps run, and the device seconds of their operations by
    part. An operation belongs to the decode program whose event holds
    its start; `while` and the like hold their bodies' operations and
    are not counted themselves."""
    steps, spans = 0, []
    for name, t0, t1 in mods:
        m = STEP_MODULE.match(name)
        if m and t0 >= w0 and t1 <= w1:
            steps += int(m.group(1) or 1)
            spans.append((t0, t1))
    seconds = dict.fromkeys(PARTS, 0.0)
    i = 0
    for name, t0, t1 in ops:
        while i < len(spans) and spans[i][1] <= t0:
            i += 1
        if i == len(spans):
            break
        if t0 < spans[i][0] or xplane.opcode(name) in CONTAINERS:
            continue
        for part, rx in patterns.items():
            if rx.search(name):
                seconds[part] += (t1 - t0) / 1e9
                break
    return {"steps": steps, "step_seconds": sum(
        t1 - t0 for t0, t1 in spans) / 1e9, "seconds": seconds}


def shapes_of(run):
    """The shapes the patterns need, from the run's configuration and
    the engine settings its traffic file gives."""
    k = run.config["builder"]["kwargs"]
    engine = {**run.config["serve"]["engine"],
              **run.workload.get("engine", {})}
    slots = 1 << (int(engine["max_sessions"]) - 1).bit_length()
    return dict(slots=slots, held=k["held"][1], d_model=k["d_model"],
                d_ff_expert=k["d_ff_expert"],
                kv_heads_full=k["kv_heads_full"],
                kv_heads_window=k["kv_heads_window"])


def _of_run(run):
    if run.device_trace is None or "held" not in run.config.get(
            "builder", {}).get("kwargs", {}):
        return None
    try:
        path = xplane.newest_xplane(profiler.trace_dir(run.cell["name"]))
    except FileNotFoundError:
        return None
    mods = modules(path)
    patterns = part_patterns(**shapes_of(run))
    chip = min(run.device_trace.devices)
    red = reduce(run.device_trace.devices[chip], mods.get(chip, []),
                 patterns, *run.trace_window_ns)
    return red if red["steps"] else None


def of_run(run):
    """`reduce` over a traced run's sub-window, once per run; None
    where there is no trace, no routed-expert configuration, or no
    decode step of a program that names its modules."""
    if not hasattr(run, "moe_trace"):
        run.moe_trace = _of_run(run)
        if run.moe_trace is not None:
            red = run.moe_trace
            run.notes["decode_step_parts"] = (
                f"{red['steps']} decode steps in the traced sub-window, "
                f"{1e3 * red['step_seconds'] / red['steps']:.3f} ms of "
                "device program a step; by shape, ms a step: " + ", ".join(
                    f"{p} {1e3 * s / red['steps']:.3f}"
                    for p, s in red["seconds"].items()))
    return run.moe_trace


def step_ms(run, part):
    red = of_run(run)
    if red is None or not red["seconds"][part]:
        return None
    return 1e3 * red["seconds"][part] / red["steps"]
