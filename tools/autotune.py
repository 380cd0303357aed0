"""Cost-model-guided autotuner CLI (ISSUE 9; ROADMAP items 2 + 5).

Searches the step knob space (slot dtype x BN-stats dtype x XLA
profile x accum geometry x scan-level remat policy x Pallas blocks)
for a model WITHOUT a chip: candidates are scored by the CPU-side HLO
meter + a roofline cost model (`singa_tpu.tuning`), the winner is
persisted to the tuned-config store that the serving tier loads by
default, and every candidate streams to a JSONL.

    python tools/autotune.py --model resnet --budget 16
    python tools/autotune.py --model tiny-cnn --budget 8 --platform cpu
    python tools/autotune.py --model resnet --pallas-jsonl \
        metrics/pallas_sweep.jsonl       # Pallas axis joins the search

Fully deterministic under --seed: same seed, same proposals, same
winner. Prints one final JSON line on stdout; progress goes to
stderr.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, ".."))
sys.path.insert(0, ROOT)


def log(msg):
    print(f"[autotune {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def _setup_platform(platform, devices=0):
    """Force a jax platform before backend init. `devices` > 0
    requests that many VIRTUAL host devices (CPU only) so the
    multi-axis mesh-geometry knobs (ISSUE 10) can be scored without a
    chip — must land in XLA_FLAGS before the backend client exists."""
    if devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}"
        ).strip()

    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    return jax


def _factories(args):
    """(model_factory, make_inputs, alias) for --model. Factories are
    deterministic: fixed RNG seeds, fresh instances per call (the
    scorer's contract)."""
    import numpy as np

    from singa_tpu import device, layer, model, opt, tensor

    dev = device.get_default_device()
    batch = args.batch

    if args.model == "resnet":
        sys.path.insert(0, os.path.join(ROOT, "examples", "cnn"))
        sys.path.insert(0, os.path.join(ROOT, "examples", "cnn",
                                        "model"))
        import resnet as resnet_mod

        size = args.image_size

        def model_factory():
            dev.SetRandSeed(7)
            return (resnet_mod.create_model(depth=args.depth),
                    opt.SGD(lr=0.1, momentum=0.9))

        def make_inputs():
            rs = np.random.RandomState(0)
            x = tensor.from_numpy(
                rs.randn(batch, 3, size, size).astype(np.float32))
            y = tensor.from_numpy(
                rs.randint(0, 1000, batch).astype(np.int32))
            return [x, y]

        # both granularities: the depth-keyed name AND the plain
        # "resnet" a caller resolves before a model exists
        return model_factory, make_inputs, [f"resnet-{args.depth}",
                                            "resnet"]

    if args.model == "tiny-cnn":
        from singa_tpu import autograd

        class TinyCNN(model.Model):
            def __init__(self):
                super().__init__(name="tiny_cnn")
                self.conv1 = layer.Conv2d(8, 3, padding=1)
                self.bn1 = layer.BatchNorm2d()
                self.conv2 = layer.Conv2d(8, 3, padding=1)
                self.relu = layer.ReLU()
                self.flat = layer.Flatten()
                self.fc = layer.Linear(10)

            def forward(self, x):
                h = self.relu(self.bn1(self.conv1(x)))
                h = self.relu(self.conv2(h))
                return self.fc(self.flat(h))

            def train_one_batch(self, x, y):
                out = self.forward(x)
                loss = autograd.softmax_cross_entropy(out, y)
                self._optimizer.backward_and_update(loss)
                return out, loss

        def model_factory():
            dev.SetRandSeed(7)
            return TinyCNN(), opt.SGD(lr=0.1, momentum=0.9)

        def make_inputs():
            rs = np.random.RandomState(0)
            x = tensor.from_numpy(
                rs.randn(batch, 3, 8, 8).astype(np.float32))
            y = tensor.from_numpy(
                rs.randint(0, 10, batch).astype(np.int32))
            return [x, y]

        return model_factory, make_inputs, ["tiny-cnn"]

    if args.model == "pipe-mlp":
        # Multi-axis workload (ISSUE 10): a PipelineStack + MoE MLP
        # whose program genuinely changes under the mesh_geometry /
        # pipeline_microbatches / moe_capacity_factor knobs — the
        # model the multi-axis search smoke exercises on the
        # 8-virtual-device CPU mesh (--devices 8 --platform cpu).
        from singa_tpu import autograd

        class PipeMLP(model.Model):
            def __init__(self):
                super().__init__(name="pipe_mlp")
                self.stack = layer.PipelineStack.mlp(4)
                self.moe = layer.MoE(4, 32)
                self.fc = layer.Linear(10)

            def forward(self, x):
                return self.fc(self.moe(self.stack(x)))

            def train_one_batch(self, x, y):
                out = self.forward(x)
                loss = autograd.softmax_cross_entropy(out, y)
                loss = autograd.add(loss, autograd.mul(
                    self.moe.aux_loss, np.float32(0.01)))
                self._optimizer.backward_and_update(loss)
                return out, loss

        def model_factory():
            dev.SetRandSeed(7)
            return PipeMLP(), opt.SGD(lr=0.1, momentum=0.9)

        def make_inputs():
            rs = np.random.RandomState(0)
            x = tensor.from_numpy(
                rs.randn(batch, 16).astype(np.float32))
            y = tensor.from_numpy(
                rs.randint(0, 10, batch).astype(np.int32))
            return [x, y]

        return model_factory, make_inputs, ["pipe-mlp"]

    if args.model == "mlp":
        from singa_tpu import autograd

        class MLP(model.Model):
            def __init__(self):
                super().__init__(name="tune_mlp")
                self.fc1 = layer.Linear(64)
                self.relu = layer.ReLU()
                self.fc2 = layer.Linear(10)

            def forward(self, x):
                return self.fc2(self.relu(self.fc1(x)))

            def train_one_batch(self, x, y):
                out = self.forward(x)
                loss = autograd.softmax_cross_entropy(out, y)
                self._optimizer.backward_and_update(loss)
                return out, loss

        def model_factory():
            dev.SetRandSeed(7)
            return MLP(), opt.SGD(lr=0.1, momentum=0.9)

        def make_inputs():
            rs = np.random.RandomState(0)
            x = tensor.from_numpy(
                rs.randn(batch, 32).astype(np.float32))
            y = tensor.from_numpy(
                rs.randint(0, 10, batch).astype(np.int32))
            return [x, y]

        return model_factory, make_inputs, ["mlp"]

    raise SystemExit(f"unknown --model {args.model!r}")


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="resnet",
                   choices=["resnet", "tiny-cnn", "mlp", "pipe-mlp"])
    p.add_argument("--devices", type=int, default=0,
                   help="force N virtual host devices (CPU) so the "
                   "multi-axis mesh-geometry knobs score without a "
                   "chip; 0 = whatever the backend has")
    p.add_argument("--depth", type=int, default=18,
                   help="resnet depth (18 keeps the CPU search fast; "
                   "the fingerprint keys per depth)")
    p.add_argument("--batch", type=int, default=8,
                   help="effective batch the search optimizes for")
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--budget", type=int, default=16,
                   help="max candidates scored (default included)")
    p.add_argument("--seed", type=int, default=0,
                   help="proposal seed — the ONLY source of search "
                   "randomness; same seed, same winner")
    p.add_argument("--chip", default="",
                   help="CHIP_SPECS key to model (default: detect "
                   "from the backend, TPU kinds normalize; CPU "
                   "backends model the v5e target unless --chip cpu)")
    p.add_argument("--store", default="",
                   help="tuned-config store path (default: "
                   "$SINGA_TPU_TUNED_STORE or .tuned/"
                   "tuned_configs.json)")
    p.add_argument("--jsonl", default="",
                   help="search-candidate JSONL (default: metrics/"
                   "autotune_<model>.jsonl)")
    p.add_argument("--pallas-jsonl", default="",
                   help="per-config sweep JSONL from benchmarks/"
                   "pallas_tune.py --jsonl: arms the Pallas "
                   "block-shape axis with measured timings")
    p.add_argument("--metrics-jsonl", default="",
                   help="metrics JSONL whose records carry a config "
                   "dict: measured examples/sec override the cost "
                   "model on exact matches")
    p.add_argument("--platform", default="",
                   help="force a jax platform before backend init "
                   "(e.g. cpu — the CI path)")
    p.add_argument("--no-store", action="store_true",
                   help="search only; do not persist the winner")
    args = p.parse_args()

    jax = _setup_platform(args.platform, devices=args.devices)
    from singa_tpu import tuning

    d = jax.devices()[0]
    detected = tuning.normalize_chip(
        f"{d.platform} {getattr(d, 'device_kind', '')}")
    # a CPU backend is almost always a stand-in for the target chip:
    # model the v5e unless the operator explicitly asks for cpu
    chip = args.chip or ("v5e" if detected == "cpu" else detected)
    log(f"backend {d.platform!r} -> modelling chip {chip!r}")

    measured = tuning.MeasuredScores()
    if args.pallas_jsonl:
        tuning.ingest_pallas_jsonl(args.pallas_jsonl, into=measured)
        log(f"pallas sweep: {measured.pallas_knobs_swept() or 'none'}")
    if args.metrics_jsonl:
        # chip/batch-gated: a CPU toy-geometry measurement must never
        # override a candidate scored for the chip being tuned
        tuning.ingest_metrics_jsonl(args.metrics_jsonl, into=measured,
                                    chip=chip, batch=args.batch)

    model_factory, make_inputs, aliases = _factories(args)
    alias = aliases[0]
    scorer = tuning.CostModelScorer(
        model_factory, make_inputs, chip=chip,
        measured=measured if (args.pallas_jsonl
                              or args.metrics_jsonl) else None)
    jsonl = args.jsonl or os.path.join(
        ROOT, "metrics", f"autotune_{args.model}.jsonl")

    t0 = time.time()
    result = tuning.autotune(scorer, budget=args.budget,
                             seed=args.seed, jsonl_path=jsonl,
                             log=log)
    took = time.time() - t0
    best = result["best_row"]
    log(f"winner ({took:.1f}s, {result['evaluated']} candidates): "
        f"score {result['best_score']:.1f} vs default "
        f"{result['default_score']:.1f} — "
        f"{tuning._fmt_cfg(result['best'])}")

    store_path = args.store or tuning.default_store_path()
    entry = None
    if not args.no_store:
        store = tuning.TunedStore(store_path)
        entry = store.put(
            scorer.fingerprint, chip, result["best"],
            result["best_score"], alias=aliases,
            provenance={
                "source": best.get("source", "cost-model"),
                "tool": "tools/autotune.py",
                "model": args.model,
                "alias": alias,
                "seed": args.seed,
                "budget": args.budget,
                "effective_batch": best.get("effective_batch"),
                "jsonl": os.path.relpath(jsonl, ROOT)
                if jsonl.startswith(ROOT) else jsonl,
            })
        log(f"persisted to {store.path} as {alias}@{chip}")

    print(json.dumps({
        "ok": True,
        "model": args.model,
        "alias": alias,
        "chip": chip,
        "fingerprint": scorer.fingerprint,
        "best": result["best"],
        "best_score": round(result["best_score"], 2),
        "default_score": round(result["default_score"], 2),
        "beats_default": result["beats_default"],
        "best_bytes": best.get("bytes"),
        "default_bytes": result["default_row"].get("bytes"),
        "best_peak_bytes": best.get("peak_bytes"),
        "evaluated": result["evaluated"],
        "seconds": round(took, 1),
        "store": (store_path if not args.no_store else None),
        "jsonl": jsonl,
    }, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
