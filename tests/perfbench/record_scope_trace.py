"""Run a training cell traced and cut a few steps out of its trace,
with the program's scope map beside them.

    python tests/perfbench/record_scope_trace.py <cell> <seed> <out.json.gz>

On a TPU, from the root of a checkout. The scope map comes from the
program that ran (`hlo_profile.step_programs()`), so the traced run and
the cut are one process: this starts `perfbench.run`'s `main` for the
cell with `--trace 1` (its result line is printed as ever), then reads
that run's .xplane.pb from `.perfbench_trace/<cell>/`. Written out, as
plain lists in whole ns from the excerpt's start: STEPS consecutive
executions of the step's module on the lowest-numbered chip with the
device operations inside them (each named by the head of its
instruction, `%name = shape opcode(`, which is all a reduction reads),
the "XLA Modules" events, the program's `singa:step.*` phases that
touch the excerpt, and the map (instruction -> [shape, opcode, scope,
dir]). With them goes what `scope_trace.reduce` and the phase medians
make of exactly that excerpt, which is also printed: the values
`test_perfbench_scope_trace.py` holds the reduction to. Not collected
by pytest, never reached from the benchmark's command.
(`fixtures/v5e_gpt2_train.scopes.json.gz` was cut so in PR 37: two
steps hold 13,930 device operations, 1.9 MB as JSON.)
"""
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

STEPS = 2


def head(name):
    """`%fusion.7 = f32[8]{0} fusion(` of an event's whole instruction."""
    from singa_tpu import hlo_profile

    m = (hlo_profile._INSTR_RE.match(name)
         or hlo_profile._TUPLE_INSTR_RE.match(name))
    return name[:m.end()] if m else name


def excerpt(cell, devices, modules, spans, smap, scope_times, steps=STEPS):
    from perfbench.harness import numbers, scope_trace
    from singa_tpu import hlo_profile

    chip = min(devices)
    mine = [m for m in modules[chip]
            if m[0].split("(")[0] == smap["module"]]
    if len(mine) <= steps + 1:
        sys.exit(f"the trace holds {len(mine)} executions of "
                 f"{smap['module']!r}")
    first = len(mine) // 2
    w0, w1 = mine[first][1], mine[first + steps][1]
    ops = {chip: [(head(n), int(a - w0), int(b - w0))
                  for n, a, b in devices[chip] if w0 <= a < w1]}
    mods = {chip: [(n, int(a - w0), int(b - w0))
                   for n, a, b in modules[chip] if w0 <= a < w1]}
    cut = [(n, int(a - w0), int(b - w0)) for n, a, b in spans
           if b > w0 and a < w1 and n.startswith("step.")]
    w = int(w1 - w0)
    red = scope_trace.reduce(ops, mods, [smap], scope_times, 0, w)
    used = {hlo_profile._event_instr(n)[0] for n, _, _ in ops[chip]}
    slim = {"module": smap["module"], "scoped": smap["scoped"],
            "unscoped": smap["unscoped"],
            "instructions": {k: [v["shape"], v["opcode"], v["scope"],
                                 v["dir"]]
                             for k, v in smap["instructions"].items()
                             if k in used}}
    inside = {name: [b - a for n, a, b in cut
                     if n == name and 0 <= a and b <= w]
              for name in (scope_trace.CALL, scope_trace.PLACE)}
    return {"cell": cell, "window_ns": [0, w], "steps": steps,
            "devices": ops, "modules": mods, "spans": cut, "map": slim,
            "printed": {
                "rows": sorted(([s, d, ns] for (s, d), ns in
                                red["rows"].items()), key=lambda r: -r[2]),
                "unplaced": red["unplaced"],
                "unplaced_by_opcode": red["unplaced_by_opcode"],
                "total": red["total"], "elsewhere": red["elsewhere"],
                "matched": red["matched"], "unmatched": red["unmatched"],
                "scoped_pct": scope_trace.scoped_pct(red),
                "phase_ms_p50": {k: numbers.median(v) / 1e6 if v else None
                                 for k, v in inside.items()},
                "idle_place_pct": scope_trace.idle_under_pct(
                    ops, cut, scope_trace.PLACE, 0, w)}}


def main(cell, seed, out):
    from perfbench import run as run_mod

    rc = run_mod.main(["--workload", cell, "--seed", seed,
                       "--seconds", "30", "--trace", "1"])
    if rc:
        sys.exit(rc)
    from perfbench.harness import profiler, scope_trace, xplane
    from singa_tpu import hlo_profile

    maps = [hlo_profile.scope_map(text)
            for _, text in hlo_profile.step_programs()]
    if not maps:
        sys.exit("the program handed over no step program")
    smap = max(maps, key=lambda m: len(m["instructions"]))
    devices, modules, spans = scope_trace.load(
        xplane.newest_xplane(profiler.trace_dir(cell)))
    rec = excerpt(cell, devices, modules, spans, smap,
                  hlo_profile.scope_times)
    with gzip.GzipFile(out, "wb", mtime=0) as f:
        f.write(json.dumps(rec, separators=(",", ":")).encode())
    print(out, os.path.getsize(out), "bytes;",
          sum(map(len, rec["devices"].values())), "device operations,",
          len(rec["map"]["instructions"]), "instructions in the map,",
          len(rec["spans"]), "phases")
    print(json.dumps(rec["printed"])[:6000])


if __name__ == "__main__":
    main(*sys.argv[1:4])
