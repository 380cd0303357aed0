"""perfbench — the repo's yardstick (BENCHMARK.json names its cells).

One command runs one cell once: `python -m perfbench.run --workload
<cell> --seed <n> --seconds <s> --trace <0|1>`. Everything that belongs
to one configuration, one traffic mix or one per-layer metric is a file
of its own, found by the name BENCHMARK.json gives it:

  configs/<config>.json        sizes, builder, the driver sections
  reference/<name>.py          plain float32 jax.numpy forward
  workloads/<cell>.json        traffic parameters for harness/traffic.py
  layer_metrics/<metric>.py    read(run) -> float | None
  drivers/<kind>.py            one kind of job (train, serve)
  harness/                     the yardstick: generator, reduction,
                               peaks, op counts, comparison

From the program it takes only the system under test and its spans,
counters and kernel names.
"""
