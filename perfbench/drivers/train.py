"""The training job: `Model.compile(use_graph=True)` and `model(x, y)`
in a user's loop, on a pool of seeded batches.

The loop dispatches as a user's does (jax returns before the device is
done) and reads the loss every `loss_every` steps, which is the only
point where the host waits for the device. The window opens after a
fenced warm-up and closes at the first loss read at or after
`--seconds`: every step counted has its result on the host side of a
fence, and the rate is steps * items / (that read - open), so it does
not move in whole read intervals.
"""
import time

import numpy as np

from perfbench.harness import cell as cell_mod
from perfbench.harness import compare, numbers, opcount, profiler, traffic

UNATTRIBUTED_GAP = "host-loop"


def build(run):
    """Model, optimizer and the device pool, from the seed."""
    from singa_tpu import device, tensor

    sec, w = run.config["train"], run.workload
    cell_mod.set_policies(sec)
    dev = device.create_tpu_device()
    dev.SetRandSeed(run.seed)
    model = cell_mod.build(run.config["builder"])
    model.set_optimizer(cell_mod.build(sec["optimizer"]))
    # exactly as examples/cnn/train_cnn.py feeds a step: host arrays
    # through tensor.from_numpy onto the device; no sharding of the
    # benchmark's own, so what the program does with a batch that sits
    # on one chip stays inside the measured step
    pool = [[tensor.from_numpy(a, device=dev) for a in batch]
            for batch in traffic.batches(w, run.seed)]
    layout = {}
    if "plan" in w:
        from singa_tpu.parallel import ParallelPlan

        layout["plan"] = ParallelPlan(**w["plan"])
    model.compile([pool[0][0]], is_train=True, use_graph=True, **layout)
    return model, pool


def reference_check(run, model, pool):
    """Eval-mode logits of the program against the plain reference, on
    the first `examples` rows of the pool's first batch, `chunk` rows
    at a time (so the check stays under the step's own memory peak)."""
    from singa_tpu import tensor

    chk = run.config["train"]["check"]
    ref = cell_mod.module("reference", run.config["reference"]["module"])
    states = {k: v.data for k, v in model.get_states().items()}
    x = pool[0][0]
    worst, ok = 0.0, True
    model.eval()
    try:
        for i in range(0, chk["examples"], chk["chunk"]):
            rows = tensor.from_raw(x.data[i:i + chk["chunk"]], x.device)
            got = model(rows).data
            # through the host: the reference is given plain values,
            # not the program's placement of them
            want = ref.logits(states, np.asarray(rows.data),
                              **run.config["reference"].get("kwargs", {}))
            good, err = compare.logits_agree(got, want, chk["tolerance"])
            ok, worst = ok and good, max(worst, err)
    finally:
        model.train()
    run.notes["reference_check"] = (
        f"max|program - reference| / max|reference| = {worst:.3e} over "
        f"{chk['examples']} examples (tolerance {chk['tolerance']})")
    if not ok:
        run.wrong.append(f"eval logits off the reference by {worst:.3e} "
                         f"of its scale (tolerance {chk['tolerance']})")


def loop(run, model, pool, seconds, step0=0):
    """Dispatch steps until a loss read lands at or after `seconds`.
    Returns (steps, elapsed, losses read, seconds each `model(x, y)`
    took to return)."""
    every = int(run.workload["loss_every"])
    losses, dispatch = [], []
    t_open = time.perf_counter()
    i = 0
    while True:
        batch = pool[(step0 + i) % len(pool)]
        with run.annotate("model(x, y)"):
            t0 = time.perf_counter()
            _, loss = model(*batch)
            dispatch.append(time.perf_counter() - t0)
        i += 1
        if i % every == 0:
            with run.annotate("loss read"):
                losses.append(float(loss.to_numpy()))
            now = time.perf_counter()
            if now - t_open >= seconds:
                return i, now - t_open, losses, dispatch


def run(run):
    w = run.workload
    model, pool = build(run)
    run.mark("model, optimizer and batch pool")
    # warm-up: the step program (compiled, or loaded from the cache),
    # then one read interval of the loop itself; never more steps in
    # flight than the window will have (each holds its outputs)
    _, loss = model(*pool[0])
    first = float(loss.to_numpy())
    run.mark("first step (compile or cache load)")
    loop(run, model, pool, 0.0, 1)
    reference_check(run, model, pool)
    loop(run, model, pool, 0.0)        # back in train mode: same program
    run.mark("warm-up steps and reference check")

    compiles0 = run.meter.compiles
    run.end_to_end["setup_s"] = time.perf_counter() - run.t_process_start
    steps, elapsed, losses, dispatch = loop(run, model, pool, run.seconds)
    run.compiles_in_window = run.meter.compiles - compiles0

    items = int(w["batch"]) * int(w.get("items_per_example", 1))
    rate = steps * items / elapsed
    run.end_to_end["train_items_per_s"] = rate
    run.attempted, run.failed = steps, 0
    run.samples["host_dispatch_s"] = dispatch
    if not compare.losses_fall(first, losses):
        run.wrong.append(f"loss did not fall or is not finite: first of "
                         f"the run {first}, read in the window {losses}")
    flops = opcount_per_item(run.config)
    chips = run.cell["chips"]
    run.notes["train"] = (
        f"{steps} steps of {items} {run.config['item']}s in {elapsed:.3f} s: "
        f"{rate:.1f} {run.config['item']}s/s, step "
        f"{1e3 * elapsed / steps:.2f} ms (median dispatch "
        f"{1e3 * numbers.median(dispatch):.3f} ms); mfu_pct "
        f"{100 * rate * flops / (chips * run.peaks['bf16_flops_per_s']):.2f} "
        f"({flops:.4g} FLOPs/{run.config['item']}, {chips} chip(s)); loss "
        f"{first:.4f} -> {losses[-1]:.4f}")

    if run.trace:
        with profiler.DeviceTrace(run):
            t_steps, t_el, _, _ = loop(run, model, pool,
                                       float(w["trace_seconds"]), steps)
        run.notes["traced_sub_window"] = (
            f"{t_steps} steps in {t_el:.3f} s: {t_steps * items / t_el:.1f} "
            f"{run.config['item']}s/s under the profiler (untraced window: "
            f"{rate:.1f})")
        run.samples["traced_steps"] = t_steps

    if "plan" in w:
        ok, bad = compare.replicas_identical(
            [p.data for p in model.param_tensors()])
        run.notes["replicas"] = (f"{len(model.param_tensors())} parameters "
                                 f"compared across chips, {bad} differ")
        if not ok:
            run.wrong.append(f"{bad} parameter(s) differ between chips "
                             "after the window")


def opcount_per_item(config):
    """FLOPs one item needs, forward + backward, by the configuration's
    own counting function in harness/opcount.py."""
    spec = config["opcount"]
    return getattr(opcount, spec["function"])(**spec["kwargs"])
