"""Synthetic-data ResNet throughput benchmark.

Reference: `examples/cnn/benchmark.py` — the script that DEFINES the
reference's headline metric (ResNet-50 images/sec/chip on synthetic
ImageNet shapes), scaling across DistOpt ranks.

Prints per-step timings and the steady-state throughput, named with
the platform and device kind it ran on. `--dist` wraps the optimizer
in `opt.DistOpt`, which in ONE process is an identity fence by design
(`dist/communicator.py`): on a four-chip host it still trains on chip
0 only. One process drives four chips through
`Model.compile(plan=ParallelPlan(data=4))` (`parallel/trainer.py`);
`--dist` is for one-process-per-rank launches (`train_mpi.py`,
`train_multiprocess.py`).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.abspath(os.path.join(_HERE, "..", "..")))
sys.path.insert(0, os.path.join(_HERE, "model"))

from singa_tpu import device, opt, tensor  # noqa: E402


def run(depth=50, batch_size=32, steps=20, warmup=5, image_size=224,
        use_graph=True, precision="bf16", dist=False, verbose=True):
    import resnet

    import jax

    device.use_compile_cache()
    dev = device.create_tpu_device()
    dev.SetRandSeed(0)
    if precision == "bf16":
        # bf16 AMP compute policy + bf16 MXU passes (see
        # tensor.set_compute_dtype; params/BN stats/loss stay fp32)
        tensor.set_matmul_precision("default")
        tensor.set_compute_dtype("bfloat16")

    m = resnet.create_model(depth=depth)
    sgd = opt.SGD(lr=0.1, momentum=0.9)
    if dist:
        sgd = opt.DistOpt(sgd)
    m.set_optimizer(sgd)

    rs = np.random.RandomState(0)
    x_np = rs.randn(batch_size, 3, image_size, image_size).astype(np.float32)
    y_np = rs.randint(0, 1000, batch_size).astype(np.int32)
    tx = tensor.from_numpy(x_np, device=dev)
    ty = tensor.from_numpy(y_np, device=dev)

    m.compile([tx], is_train=True, use_graph=use_graph)
    # warmup (incl. XLA compile), then pipelined timing blocks: enqueue
    # several steps and block once — per-step waits would measure the
    # host<->device round trip, not the device.
    for _ in range(max(2, warmup)):
        out, loss = m(tx, ty)
    loss.data.block_until_ready()
    times = []
    done = 0
    while done < steps:
        n = min(10, max(4, steps - done))
        t0 = time.time()
        for _ in range(n):
            out, loss = m(tx, ty)
        jax.block_until_ready(
            [p.data for p in m.param_tensors()] + [loss.data])
        dt = (time.time() - t0) / n
        times.append(dt)
        done += n
        if verbose:
            print(f"{n}-step block: {dt * 1e3:.1f} ms/step "
                  f"({batch_size / dt:.1f} img/s) "
                  f"loss {float(loss.to_numpy()):.3f}")
    med = sorted(times)[len(times) // 2]
    ips = batch_size / med
    if verbose:
        d = dev.jax_device
        print(f"ResNet-{depth} bs={batch_size} {image_size}x{image_size} "
              f"{precision}: {ips:.1f} images/sec on {d.platform} "
              f"({d.device_kind})")
    return ips


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--depth", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--precision", choices=["fp32", "bf16"], default="bf16")
    p.add_argument("--no-graph", dest="graph", action="store_false",
                   default=True)
    p.add_argument("--dist", action="store_true")
    p.add_argument("--json", action="store_true")
    a = p.parse_args()
    ips = run(a.depth, a.batch_size, a.steps, image_size=a.image_size,
              use_graph=a.graph, precision=a.precision, dist=a.dist,
              verbose=not a.json)
    if a.json:
        import jax

        d = jax.devices()[0]
        print(json.dumps({"metric": f"resnet{a.depth}_images_per_sec",
                          "value": round(ips, 2), "unit": "img/s",
                          "platform": d.platform,
                          "device_kind": d.device_kind}))
