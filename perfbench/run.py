"""Run one cell of BENCHMARK.json once.

    python -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it refuses to run without a TPU (exit 2, no result line),
keeps jax's persistent compile cache at `device.use_compile_cache()`'s
fixed path inside the checkout, and hands the cell to the driver its
workload file names. Human-readable lines go first; the LAST stdout
line is the result: `correct`, `attempted`, `failed`, `metrics`,
`device` (and `breakdown` on a traced run). With `--trace 0` the
metrics are the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

# neither imports jax: the chip is first touched in main()
from perfbench.harness import cell as cell_mod  # noqa: E402
from perfbench.harness import xplane  # noqa: E402


def say(msg):
    print(f"[perfbench] {msg}", flush=True)


def layer_metrics(run, entries):
    """Each per-layer metric through its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for entry in entries:
        value = cell_mod.module("layer_metrics", entry["name"]).read(run)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def end_to_end_metrics(run, entries):
    out = {}
    for entry in entries:
        if entry["name"] not in run.end_to_end:
            raise KeyError(
                f"cell {run.cell['name']!r} is to report "
                f"{entry['name']!r} and its driver did not measure it")
        out[entry["name"]] = {"value": float(run.end_to_end[entry["name"]]),
                              "unit": entry["unit"]}
    return out


def device_record(run, devices):
    stats = [d.memory_stats() or {} for d in devices]
    rec = {"platform": devices[0].platform,
           "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": max(
               int(s.get("peak_bytes_in_use", 0)) for s in stats)}
    if run.device_trace is not None:
        w0, w1 = run.trace_window_ns
        busy = xplane.busy_by_chip(run.device_trace, w0, w1)
        rec["busy_s"] = sum(busy.values()) / len(busy)
        rec["window_s"] = (w1 - w0) / 1e9
    return rec


def breakdown(run, unattributed):
    w0, w1 = run.trace_window_ns
    return {"device_ops": xplane.top_ops(run.device_trace, w0, w1, 10),
            "idle_gaps": xplane.idle_gaps(run.device_trace, w0, w1, 5,
                                          unattributed)}


def main(argv=None):
    ap = argparse.ArgumentParser(prog="perfbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, config, workload = cell_mod.load_cell(args.workload)
    if cell_mod.ROOT not in sys.path:
        sys.path.insert(0, cell_mod.ROOT)
    try:
        from singa_tpu import device
    except ImportError as e:
        print(f"[perfbench] the program is not in this checkout: {e}",
              file=sys.stderr)
        return 3

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"[perfbench] cell {cell['name']!r} needs {cell['chips']} TPU "
              f"chip(s); jax found {len(devices)} device(s) of platform "
              f"{devices[0].platform!r}. There is no CPU mode.",
              file=sys.stderr)
        return 2

    from perfbench.harness import compile_meter, peaks

    run = cell_mod.Run(cell=cell, config=config, workload=workload,
                       seconds=args.seconds, trace=bool(args.trace),
                       seed=args.seed, t_process_start=T_START,
                       meter=compile_meter.CompileMeter(),
                       peaks=peaks.for_kind(devices[0].device_kind))
    cache_dir = device.use_compile_cache()
    # every program, however quick to compile, comes from the cache
    # after a cell's first run (jax keeps only those over 1 s by default)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}; {len(devices)} x {devices[0].device_kind}; "
        f"compile cache {cache_dir}")
    run.mark("import and reaching the chip")

    driver = cell_mod.module("drivers", workload["driver"])
    driver.run(run)
    result = result_line(run, devices, driver.UNATTRIBUTED_GAP)
    say(f"built {run.meter.compiles} executable(s), "
        f"{run.meter.cache_hits} from the compile cache; wall "
        f"{time.perf_counter() - T_START:.1f} s")
    print(json.dumps(result), flush=True)
    return 0


def result_line(run, devices, unattributed, root=None):
    """The contract's last line from what the driver gathered; the
    human-readable lines are printed on the way."""
    root = root or cell_mod.ROOT
    name = run.cell["name"]
    if run.compiles_in_window:
        run.wrong.append(f"{run.compiles_in_window} executable(s) built "
                         "inside the measured window")
    if run.trace:
        metrics = layer_metrics(
            run, cell_mod.metrics_for(name, "per_layer", root))
    else:
        metrics = end_to_end_metrics(
            run, cell_mod.metrics_for(name, "end_to_end", root))
    if run.marks:
        run.notes["setup"] = run.setup_phases()
    for k, v in sorted(run.notes.items()):
        say(f"{k}: {v}")
    for why in run.wrong:
        say(f"NOT CORRECT: {why}")
    result = {"correct": not run.wrong, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics,
              "device": device_record(run, devices)}
    if run.device_trace is not None:
        result["breakdown"] = breakdown(run, unattributed)
    return result


if __name__ == "__main__":
    sys.exit(main())
