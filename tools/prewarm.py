"""Offline serving prewarm: populate the AOT export cache with the
eval-forward executable for every (model, bucket) pair a serving
config will need, so a serving worker's cold start is
DESERIALIZE-only — the request path never traces (ISSUE 7 satellite;
`singa_tpu.serve.prewarm_forward` does the work, this is the CLI).

    # an ONNX model: input shapes/dtypes come from the graph itself
    python tools/prewarm.py --onnx model.onnx --max-batch 64

    # a user model factory ("module:callable" returning a Model whose
    # params are initialized or initializable from the given inputs)
    python tools/prewarm.py --factory examples.mlp.model:create \
        --input-shape 784 --max-batch 32

    # what WOULD be built (nothing traces, nothing is written)
    python tools/prewarm.py --onnx model.onnx --max-batch 64 --dry-run

    # fleet provisioning gate (ISSUE 11): is the SHARED store ready
    # for N replicas? Verifies every (model, bucket) artifact key
    # resolves (via _JitForward.export_key — the same key the
    # dispatch path loads), exits 1 listing each miss in full
    python tools/prewarm.py --onnx model.onnx --max-batch 64 \
        --verify-store

    # int8 quantized serving (ISSUE 19): the quant knob joins
    # knob_fingerprint(), so quantized executables live under their
    # OWN keys — prewarm and verify with the mode the fleet will run
    python tools/prewarm.py --onnx model.onnx --max-batch 64 \
        --quant int8 --verify-store

`--dir` points at the artifact store (default `.export_cache/`, the
same default `SINGA_TPU_EXPORT_CACHE` uses). Exit code:
0 when every bucket is present/built, 1 when `--dry-run` /
`--verify-store` found missing artifacts (CI-able: "is this store
provisioned for this config?").

The fleet flow is populate-once-start-N: run this tool ONCE against
the shared store, point every replica at it
(`device.set_export_cache` / `SINGA_TPU_EXPORT_CACHE`), and each
replica's cold start — including a fleet-supervisor RESTART after a
replica kill — is deserialize-only (store hits, zero traces). Gate
deploys on `--verify-store` so a fleet never boots against a store
with holes.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.abspath(os.path.join(HERE, "..")))


def _parse_shape(s):
    s = s.strip()
    if not s:
        return ()
    return tuple(int(d) for d in s.split(","))


def _build_model(a):
    """(model, sample_spec) from the CLI flags."""
    import numpy as np

    from singa_tpu import tensor

    if a.onnx:
        from singa_tpu import sonnx

        m = sonnx.SONNXModel(a.onnx)
        spec = []
        for i, (shape, dtype) in enumerate(m.input_specs()):
            if shape is None:
                if not a.input_shape:
                    raise SystemExit(
                        f"prewarm: ONNX input #{i} declares no static "
                        "shape; pass --input-shape")
                shape = _parse_shape(a.input_shape[min(
                    i, len(a.input_shape) - 1)])
                dtype = a.dtype
            spec.append((shape, dtype))
        return m, spec
    if a.factory:
        import importlib

        mod_name, _, fn_name = a.factory.partition(":")
        if not fn_name:
            raise SystemExit(
                "prewarm: --factory must be 'module:callable'")
        factory = getattr(importlib.import_module(mod_name), fn_name)
        m = factory()
        if not a.input_shape:
            raise SystemExit("prewarm: --factory needs --input-shape")
        spec = [(_parse_shape(s), a.dtype) for s in a.input_shape]
        if not m.param_tensors():
            # lazy models initialize from one compile pass at bucket 1
            inputs = [tensor.from_numpy(
                np.zeros((1,) + shape, np.dtype(dtype)))
                for shape, dtype in spec]
            m.compile(inputs, is_train=False, use_graph=True)
        return m, spec
    raise SystemExit("prewarm: pass --onnx or --factory (see --help)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--onnx", help="ONNX model file to serve")
    ap.add_argument("--factory",
                    help="'module:callable' returning the Model")
    ap.add_argument("--input-shape", action="append", default=[],
                    help="per-SAMPLE input shape, comma-separated "
                    "(repeat per input; batch dim excluded)")
    ap.add_argument("--dtype", default="float32",
                    help="input dtype when not read from the graph")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="bucket ladder ceiling (default: the serving "
                    "config's max_batch)")
    ap.add_argument("--dir", default=os.environ.get(
        "SINGA_TPU_EXPORT_CACHE") or os.path.join(HERE, "..",
                                                  ".export_cache"),
                    help="artifact store directory")
    ap.add_argument("--dry-run", action="store_true",
                    help="list present/missing artifacts; trace "
                    "nothing, write nothing")
    ap.add_argument("--verify-store", action="store_true",
                    help="fleet provisioning gate: cross-check that "
                    "every (model, bucket) artifact key resolves in "
                    "the store; exit 1 listing each miss in full "
                    "(traces nothing, writes nothing)")
    ap.add_argument("--quant", choices=["off", "int8"], default="off",
                    help="arm int8 quantized inference before "
                    "building/verifying: keys carry the knob via "
                    "knob_fingerprint, so a store provisioned for "
                    "fp32 does NOT satisfy an int8 fleet (and vice "
                    "versa)")
    ap.add_argument("--cpu", action="store_true",
                    help="force the XLA CPU backend")
    a = ap.parse_args(argv)

    if a.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
        from jax.extend.backend import clear_backends

        clear_backends()

    from singa_tpu import device, serve

    device.set_export_cache(os.path.abspath(a.dir))
    if a.quant != "off":
        device.set_inference_quant(a.quant)
    m, spec = _build_model(a)
    rows = serve.prewarm_forward(
        m, spec, max_batch=a.max_batch,
        dry_run=a.dry_run or a.verify_store)
    if a.verify_store:
        # Fleet gate output: every miss in full (a deploy log must
        # name the exact keys to re-populate), then the verdict.
        misses = [r for r in rows if r["status"] == "missing"]
        for r in misses:
            seq = f" seq={r['seq']}" if r["seq"] is not None else ""
            print(f"  MISSING bucket={r['bucket']}{seq} key={r['key']}")
        if misses:
            print(f"  store NOT provisioned: {len(misses)} of "
                  f"{len(rows)} bucket artifact(s) missing from "
                  f"{os.path.abspath(a.dir)} — run tools/prewarm.py "
                  "(no --verify-store) once, then start the fleet")
            return 1
        print(f"  store provisioned: all {len(rows)} bucket "
              f"artifact(s) resolve in {os.path.abspath(a.dir)} — "
              "populate-once-start-N ready (replica cold start and "
              "restart are deserialize-only)")
        return 0
    missing = 0
    for r in rows:
        seq = f" seq={r['seq']}" if r["seq"] is not None else ""
        print(f"  bucket={r['bucket']:<5}{seq} "
              f"{r['status']:<8} {r['key'][:16]}")
        missing += r["status"] == "missing"
    built = sum(1 for r in rows if r["status"] == "built")
    present = sum(1 for r in rows if r["status"] == "present")
    print(f"  {len(rows)} bucket(s): {present} present, {built} "
          f"built, {missing} missing")
    return 1 if missing else 0


if __name__ == "__main__":
    sys.exit(main())
