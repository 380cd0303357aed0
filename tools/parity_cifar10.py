"""CIFAR-10 loss-curve parity artifact (the north star's correctness
gate; BASELINE.md row 2, VERDICT r1 next-round #7).

Reference invariant: the same CNN config must produce the same loss
trajectory on CppCPU and CudaGPU within tolerance
(test/python/test_model.py's graph-vs-eager discipline, SURVEY.md
§4.2). The TPU translation: train the CIFAR CNN config for N steps

  * on the host XLA CPU backend, eager (per-op dispatch),
  * on the host XLA CPU backend, graph mode (one jit program),
  * on the TPU chip, graph mode (skipped if the chip is unreachable —
    recorded as null),

save all curves + pairwise max relative differences to
PARITY_cifar10.json at the repo root, and fail if any available pair
diverges beyond tolerance.

Data: deterministic synthetic CIFAR-shaped batches (this environment
has no dataset downloads); the parity property is about execution
backends, not data provenance. The batches CYCLE over a small fixed
pool (VERDICT r5 next #4): fresh random batches with random labels
are unlearnable, so the old 30-step lr=0.05 run compared curves
pinned at the ln(10)=2.303 plateau — parity at a constant is weak
evidence. Cycling lets the CNN memorize the pool, the compared curve
descends >=0.5 below the plateau, and the artifact reports max_rel at
the steepest-descent region, where divergence would actually show.

Run: python tools/parity_cifar10.py [--steps N] [--skip-tpu]
"""
import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "examples", "cnn", "model"))

TOL_REL = 2e-2  # bf16-free fp32 runs track much tighter; headroom for TPU
PLATEAU = float(np.log(10.0))  # random-guess CE on 10 classes
DESCENT = 0.5  # the curve must end at least this far below the plateau
# Descent-regime defaults (VERDICT r5 next #4): lr 0.01 tames the old
# lr=0.05 step-2 loss spike (~41), 80 steps over a 4-batch pool = 20
# epochs — the CNN memorizes the pool to ~0.05 loss, far below the
# plateau, so the compared trajectory is a real descent.
STEPS, LR, POOL = 80, 0.01, 4


def train_curve(backend: str, use_graph: bool, steps: int,
                batch: int = 32, lr: float = LR, pool: int = POOL):
    """One training run; returns the per-step loss list. Batches cycle
    over a fixed `pool` so the loss can descend below the random-guess
    plateau (memorization — fresh random labels are unlearnable)."""
    import jax

    if backend == "cpu":
        jax.config.update("jax_platforms", "cpu")
        from jax.extend.backend import clear_backends

        clear_backends()

    import cnn as cnn_mod

    from singa_tpu import device, opt, tensor

    dev = (device.create_tpu_device() if backend == "tpu"
           else device.get_default_device())
    dev.SetRandSeed(7)
    m = cnn_mod.create_model(num_classes=10)
    m.set_optimizer(opt.SGD(lr=lr, momentum=0.9))

    rs = np.random.RandomState(0)
    x_np = rs.randn(pool, batch, 3, 32, 32).astype(np.float32)
    y_np = rs.randint(0, 10, (pool, batch)).astype(np.int32)

    tx = tensor.from_numpy(x_np[0], device=dev)
    m.compile([tx], is_train=True, use_graph=use_graph)
    losses = []
    for s in range(steps):
        tx = tensor.from_numpy(x_np[s % pool], device=dev)
        ty = tensor.from_numpy(y_np[s % pool], device=dev)
        out, loss = m(tx, ty)
        losses.append(float(loss.to_numpy()))
    return losses


def _curve_in_subprocess(backend, use_graph, steps, timeout):
    """Each curve runs in its own process: backend selection is global
    jax state, and a hung TPU dial must not kill the whole artifact."""
    code = (
        "import sys; sys.path.insert(0, {root!r});"
        "from tools.parity_cifar10 import train_curve;"
        "import json;"
        "print('CURVE ' + json.dumps(train_curve({backend!r}, {graph},"
        " {steps})))"
    ).format(root=_ROOT, backend=backend, graph=use_graph, steps=steps)
    try:
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True,
                             timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, "timeout"
    for line in out.stdout.splitlines():
        if line.startswith("CURVE "):
            return json.loads(line[len("CURVE "):]), None
    return None, (out.stderr or "no output")[-500:]


def max_rel_diff(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-3)))


def steepest_descent_window(curve, window: int = 5):
    """[start, end) of the `window`-step span where the curve drops
    fastest — the region where backend divergence would actually show
    (a plateau agrees trivially)."""
    c = np.asarray(curve)
    if len(c) <= window:
        return 0, len(c)
    drops = c[:-window] - c[window:]
    i = int(np.argmax(drops))
    return i, i + window


def descent_metrics(curves):
    """Descent evidence + per-pair max_rel at the steepest-descent
    region of the reference (cpu_eager) curve."""
    ref = curves.get("cpu_eager") or curves.get("cpu_graph")
    if not ref:
        return None, {}
    lo, hi = steepest_descent_window(ref)
    at_descent = {}
    for x, y in [("cpu_eager", "cpu_graph"), ("cpu_graph", "tpu_graph"),
                 ("cpu_eager", "tpu_graph")]:
        if curves.get(x) and curves.get(y):
            at_descent[f"{x}_vs_{y}"] = max_rel_diff(
                curves[x][lo:hi], curves[y][lo:hi])
    info = {
        "plateau": round(PLATEAU, 4),
        "final_loss": round(float(ref[-1]), 4),
        "min_loss": round(float(min(ref)), 4),
        "descended": bool(min(ref) <= PLATEAU - DESCENT),
        "steepest_descent_window": [lo, hi],
    }
    return info, at_descent


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=STEPS)
    ap.add_argument("--skip-tpu", action="store_true")
    ap.add_argument("--tpu-timeout", type=float, default=600.0)
    ap.add_argument("--tpu-only", action="store_true",
                    help="reuse the CPU curves already recorded in "
                    "PARITY_cifar10.json (they are deterministic: fixed "
                    "seeds, synthetic data) and run ONLY the tpu_graph "
                    "column — the fast path the staged bench uses so the "
                    "north-star gate runs FIRST in the window "
                    "(VERDICT r4 next #1)")
    ap.add_argument("--budget", type=float, default=1e9,
                    help="hard wall-clock budget (s): every subprocess "
                    "timeout is clipped so the artifact + result line "
                    "always get written before a parent gate kills us")
    a = ap.parse_args()
    t_start = time.time()

    def rem():
        return max(5.0, a.budget - (time.time() - t_start))

    curves = {}
    errors = {}
    reused = None
    if a.tpu_only:
        path = os.path.join(_ROOT, "PARITY_cifar10.json")
        try:
            with open(path) as f:
                prev = json.load(f)
            pc = prev.get("config", {})
            if (pc.get("steps") == a.steps and pc.get("lr") == LR
                    and pc.get("pool") == POOL
                    and prev.get("curves", {}).get("cpu_eager")
                    and prev.get("curves", {}).get("cpu_graph")):
                reused = {k: prev["curves"][k]
                          for k in ("cpu_eager", "cpu_graph")}
                print("reusing recorded CPU curves (deterministic)",
                      file=sys.stderr, flush=True)
        except (OSError, ValueError):
            pass
    if reused:
        curves.update(reused)
    else:
        for name, backend, graph, to in [
            ("cpu_eager", "cpu", False, 1200),
            ("cpu_graph", "cpu", True, 1200),
        ]:
            print(f"running {name}...", file=sys.stderr, flush=True)
            curves[name], err = _curve_in_subprocess(
                backend, graph, a.steps, min(to, rem()))
            if err:
                errors[name] = err
    if not a.skip_tpu:
        print("running tpu_graph...", file=sys.stderr, flush=True)
        curves["tpu_graph"], err = _curve_in_subprocess(
            "tpu", True, a.steps, min(a.tpu_timeout, rem()))
        if err:
            errors["tpu_graph"] = err
    else:
        curves["tpu_graph"] = None
        errors["tpu_graph"] = "skipped"

    diffs = {}
    pairs = [("cpu_eager", "cpu_graph"), ("cpu_graph", "tpu_graph"),
             ("cpu_eager", "tpu_graph")]
    for x, y in pairs:
        if curves.get(x) and curves.get(y):
            diffs[f"{x}_vs_{y}"] = max_rel_diff(curves[x], curves[y])
    descent, at_descent = descent_metrics(curves)

    artifact = {
        "config": {"model": "examples/cnn/model/cnn.py", "batch": 32,
                   "steps": a.steps, "lr": LR, "momentum": 0.9,
                   "pool": POOL,
                   "data": "synthetic CIFAR-shaped, seed 0, cycled "
                           f"pool of {POOL} batches",
                   "tolerance_rel": TOL_REL},
        "curves": curves, "max_rel_diffs": diffs,
        "max_rel_at_descent": at_descent, "descent": descent,
        "errors": errors,
    }
    path = os.path.join(_ROOT, "PARITY_cifar10.json")
    degrade = None
    prev = None
    try:
        with open(path) as f:
            prev = json.load(f)
        # A failed/timed-out TPU attempt must never erase a recorded
        # on-chip column (the acceptance-gate evidence): a run that
        # dies mid-curve would otherwise null out the PASSED artifact.
        if prev.get("curves", {}).get("tpu_graph") and not curves.get(
                "tpu_graph"):
            pc = prev.get("config", {})
            if (pc.get("steps"), pc.get("lr"), pc.get("pool")) == (
                    a.steps, LR, POOL):
                degrade = "recorded tpu_graph present, this run has none"
            else:
                # config upgrade (e.g. the descent-regime change): the
                # new artifact replaces the old one, but the recorded
                # on-chip evidence is preserved verbatim under
                # previous_onchip — monotone evidence, new gate.
                artifact["previous_onchip"] = {
                    "config": pc, "curves": prev.get("curves"),
                    "max_rel_diffs": prev.get("max_rel_diffs"),
                }
    except (OSError, ValueError):
        pass
    if (a.tpu_only and not (curves.get("cpu_eager")
                            and curves.get("cpu_graph"))):
        # Never overwrite a recorded artifact with an all-null one
        # (e.g. budget ran out before the CPU fallback finished).
        print(f"keeping existing {path} (no CPU curves this run)",
              file=sys.stderr)
    elif degrade:
        print(f"keeping existing {path} ({degrade})", file=sys.stderr)
    else:
        with open(path, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {path}")
    print(json.dumps({"max_rel_diffs": diffs,
                      "max_rel_at_descent": at_descent,
                      "descent": descent, "errors": errors}))

    bad = {k: v for k, v in diffs.items() if v > TOL_REL}
    bad.update({f"{k}@descent": v for k, v in at_descent.items()
                if v > TOL_REL})
    if bad:
        print(f"PARITY FAIL: {bad}", file=sys.stderr)
        sys.exit(1)
    if not diffs:
        print("PARITY FAIL: no comparable pairs", file=sys.stderr)
        sys.exit(1)
    if descent and not descent["descended"]:
        print(f"PARITY FAIL: curve never descended {DESCENT} below "
              f"the ln(10) plateau ({descent})", file=sys.stderr)
        sys.exit(1)
    print("PARITY OK")


if __name__ == "__main__":
    main()
