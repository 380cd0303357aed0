"""CNN trainer. Reference: `examples/cnn/train_cnn.py` — argparse →
device → model.compile → epoch loop, with `--graph/--no-graph`,
`--precision`, and distributed (`DistOpt`) options.

Usage:
    python train_cnn.py cnn mnist --epochs 2 --batch-size 64
    python train_cnn.py resnet cifar10 --depth 18 --graph
"""
import argparse
import os
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.abspath(os.path.join(_HERE, "..", "..")))
sys.path.insert(0, os.path.join(_HERE, "model"))
sys.path.insert(0, os.path.join(_HERE, "data"))

from singa_tpu import device, opt, tensor  # noqa: E402


def accuracy(pred, target):
    return float((pred.argmax(-1) == target).mean())


def create_model(name, **kwargs):
    import importlib

    mod = importlib.import_module(name)
    return mod.create_model(**kwargs)


def load_data(name, data_dir):
    import importlib

    return importlib.import_module(name).load(data_dir)


def run(args):
    dev = device.create_tpu_device()
    dev.SetRandSeed(args.seed)
    np.random.seed(args.seed)

    tx_np, ty_np, vx_np, vy_np = load_data(args.data, args.data_dir)
    num_classes = int(ty_np.max()) + 1

    kwargs = {"num_classes": num_classes, "num_channels": tx_np.shape[1]}
    if args.model == "resnet":
        kwargs = {"num_classes": num_classes, "depth": args.depth or 50}
    elif args.model == "vgg":
        # input channels are shape-inferred at first call (lazy init);
        # the model ctor validates depth against {11,13,16,19}
        kwargs = {"num_classes": num_classes, "depth": args.depth or 16}
    elif args.model == "mobilenet":
        kwargs = {"num_classes": num_classes}
    elif args.model == "vit":
        kwargs = {"num_classes": num_classes,
                  "img_size": tx_np.shape[-1]}
    m = create_model(args.model, **kwargs)

    if args.precision == "bf16":
        tensor.set_matmul_precision("default")
        tensor.set_compute_dtype("bfloat16")  # bf16 activations, fp32 params
        tx_np = tx_np.astype(np.float32)

    sgd = opt.SGD(lr=args.lr, momentum=0.9, weight_decay=1e-5)
    if args.dist:
        sgd = opt.DistOpt(sgd, local_rank=args.local_rank,
                          world_size=args.world_size)
        m.dist_option = args.dist_option
        m.spars = args.spars
    m.set_optimizer(sgd)

    bs = args.batch_size
    # resize input spatially when the model has a fixed-size head
    # (alexnet/xception use fixed avg-pool windows; cnn/resnet are
    # shape-agnostic)
    want = getattr(m, "input_size", tx_np.shape[-1])
    if args.model == "vgg":
        # VGG only needs its 5 stride-2 pools to survive (>=32px), not
        # the full 224 its ImageNet input_size suggests
        want = max(32, tx_np.shape[-1])
    if want != tx_np.shape[-1] and args.model in ("alexnet", "xceptionnet",
                                                  "vgg"):
        reps = max(1, want // tx_np.shape[-1] + 1)
        tx_np = np.tile(tx_np, (1, 1, reps, reps))[:, :, :want, :want]
        vx_np = np.tile(vx_np, (1, 1, reps, reps))[:, :, :want, :want]

    tx = tensor.from_numpy(tx_np[:bs], device=dev)
    ty = tensor.from_numpy(ty_np[:bs], device=dev)
    m.compile([tx], is_train=True, use_graph=args.graph)

    nbatch = len(tx_np) // bs
    for epoch in range(args.epochs):
        m.train()
        t0, tot_loss, seen = time.time(), 0.0, 0
        idx = np.random.permutation(len(tx_np))
        for b in range(nbatch):
            sel = idx[b * bs:(b + 1) * bs]
            tx.copy_from_numpy(np.ascontiguousarray(tx_np[sel]))
            ty.copy_from_numpy(np.ascontiguousarray(ty_np[sel]))
            out, loss = m(tx, ty)
            tot_loss += float(loss.to_numpy())
            seen += bs
        dt = time.time() - t0
        m.eval()
        correct, n_val = 0.0, (len(vx_np) // bs) * bs
        for b in range(len(vx_np) // bs):
            vx = tensor.from_numpy(
                np.ascontiguousarray(vx_np[b * bs:(b + 1) * bs]), device=dev)
            correct += accuracy(m(vx).to_numpy(),
                                vy_np[b * bs:(b + 1) * bs]) * bs
        acc = correct / max(n_val, 1)
        print(f"epoch {epoch}: loss {tot_loss / nbatch:.4f} "
              f"val-acc {acc:.3f}  {seen / dt:.1f} img/s")
    return tot_loss / nbatch


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("model", choices=["cnn", "alexnet", "resnet",
                                     "xceptionnet", "vgg", "mobilenet",
                                     "vit"])
    p.add_argument("data", choices=["mnist", "cifar10", "cifar100"])
    p.add_argument("--data-dir", default=None)
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.005)
    p.add_argument("--depth", type=int, default=None,
                   help="resnet: 18/34/50/101/152 (default 50); "
                        "vgg: 11/13/16/19 (default 16)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--graph", action="store_true", default=True)
    p.add_argument("--no-graph", dest="graph", action="store_false")
    p.add_argument("--precision", choices=["fp32", "bf16"], default="fp32")
    p.add_argument("--dist", action="store_true",
                   help="wrap the optimizer in opt.DistOpt: for "
                   "one-process-per-rank launches (train_mpi.py, "
                   "train_multiprocess.py). In ONE process DistOpt is "
                   "an identity fence — on a four-chip host this flag "
                   "still trains on chip 0 only; one process drives "
                   "four chips through Model.compile(plan="
                   "ParallelPlan(data=4))")
    p.add_argument("--dist-option", default="plain",
                   choices=["plain", "half", "partialUpdate",
                            "sparseTopK", "sparseThreshold"])
    p.add_argument("--spars", type=float, default=0.05)
    p.add_argument("--local-rank", type=int, default=0)
    p.add_argument("--world-size", type=int, default=None)
    run(p.parse_args())
