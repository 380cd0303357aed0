"""Cut three dispatcher cycles out of a traced serving run's trace.

    python tests/perfbench/record_program_trace.py <cell> <out.json>

Run it in the checkout where `python3 -m perfbench.run --workload
<cell> ... --trace 1` has just run on a TPU: that run's .xplane.pb is
still under `.perfbench_trace/<cell>/`. Writes three consecutive
cycles of the decode dispatcher, the first with a prefill in it where
there is one, as plain [name, t0, t1] lists in whole ns from the
excerpt's start: the program's "singa:" spans, and the device's
operations merged where they touch or overlap (a cycle holds some
7,000 of them, and the reductions read only when the chip was busy and
when it stopped). With them goes what `program_trace.reduce` makes of
exactly that excerpt, which is also printed, after a check that the
unmerged operations reduce to the same: the values
`test_perfbench_program_trace.py` holds the reduction to. Not collected
by pytest, never reached from the benchmark's command.
(`fixtures/v5e_gpt2-serve-*.cycles.json` were cut so in PR 24.)
"""
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CYCLES = 3


def main(cell, out):
    from perfbench.harness import profiler, program_trace, xplane

    path = xplane.newest_xplane(profiler.trace_dir(cell))
    devices, spans = program_trace.load(path)
    starts = [s[1] for s in spans if s[0] == "decode.admit"]
    if len(starts) <= CYCLES:
        sys.exit(f"{path} holds {len(starts)} dispatcher cycles")
    prefills = [s[1] for s in spans if s[0] == "decode.prefill.dispatch"]
    # from the middle of the trace on, the cycle of the first prefill
    mid = starts[len(starts) // 2]
    target = min((p for p in prefills if p >= mid), default=mid)
    first = min(max(i for i, t in enumerate(starts) if t <= target),
                len(starts) - CYCLES - 1)
    w0, w1 = starts[first], starts[first + CYCLES]
    raw = {chip: [o for o in ops if o[2] > w0 and o[1] < w1]
           for chip, ops in devices.items()}
    rec = excerpt(cell, raw, spans, w0, w1)
    red = rec["printed"]
    unmerged = program_trace.reduce(raw, spans, w0, w1)
    for a, b in [(red["readback_tail_ms_p50"],
                  unmerged["readback_tail_ms_p50"])] + [
            (red["idle_pct"][k], unmerged["idle_pct"][k])
            for k in red["idle_pct"]]:
        if not math.isclose(a, b, rel_tol=1e-6, abs_tol=1e-9):
            sys.exit(f"merging the device's operations changed the "
                     f"reduction: {red} != {unmerged}")
    with open(out, "w") as f:
        json.dump(rec, f, separators=(",", ":"))
    print(out, os.path.getsize(out), "bytes;", sum(map(len, raw.values())),
          "device operations in",
          sum(map(len, rec["devices"].values())), "busy intervals,",
          len(rec["spans"]), "spans")
    print(json.dumps(red))
    print(program_trace.describe(red))


def excerpt(cell, devices, spans, w0, w1):
    from perfbench.harness import program_trace, xplane

    busy = {chip: [("busy", int(a - w0), int(b - w0)) for a, b in
                   xplane.merge(xplane.clip([(o[1], o[2]) for o in ops],
                                            w0, w1))]
            for chip, ops in devices.items()}
    spans = [(n, int(t0 - w0), int(t1 - w0)) for n, t0, t1 in spans
             if t1 > w0 and t0 < w1]
    w = int(w1 - w0)
    return {"cell": cell, "window_ns": [0, w], "devices": busy,
            "spans": spans,
            "printed": program_trace.reduce(busy, spans, 0, w)}


if __name__ == "__main__":
    main(*sys.argv[1:3])
