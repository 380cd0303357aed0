"""Pallas kernel block-shape tuner (VERDICT r4 next #3; ISSUE 9).

Sweeps the env-overridable tiling knobs in
`singa_tpu/ops/pallas_kernels.py` by re-running the relevant
`pallas_micro.py` measurements in subprocesses (the knobs are read at
import), and prints a winners table.  Run ON the chip:

    python benchmarks/pallas_tune.py

or WITHOUT one (ISSUE 9): `--cpu` forces the jax CPU backend, where
the kernels run in Pallas interpret mode at reduced shapes — absolute
microseconds are meaningless there, but the RELATIVE ranking across
block shapes is what the autotuner needs, and `--jsonl PATH` emits
one record per (case, knob, value) that
`singa_tpu.tuning.ingest_pallas_jsonl` ingests as a measured score
source — the Pallas block-shape axis joins the knob search with no
chip in the loop:

    python benchmarks/pallas_tune.py --cpu --jsonl metrics/pallas_sweep.jsonl
    python tools/autotune.py --model resnet --pallas-jsonl metrics/pallas_sweep.jsonl

If a knob setting pushes a currently-losing kernel past 1.1x XLA
ON-CHIP, bake it in as the default in pallas_kernels.py and re-run
pallas_micro.py to refresh PALLAS_BENCH.md; otherwise the per-kernel
default-off policy stands (see the policy note in pallas_kernels.py).
Interpret-mode ratios never justify a bake-in.
"""
import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, ".."))

CASE_SRC = r"""
import json, os, sys, time
sys.path.insert(0, {root!r})
if os.environ.get("PALLAS_TUNE_PLATFORM"):
    # pin the backend before anything creates one
    import jax
    jax.config.update("jax_platforms",
                      os.environ["PALLAS_TUNE_PLATFORM"])
import numpy as np
import jax, jax.numpy as jnp
from singa_tpu.ops import pallas_kernels as pk

SMALL = {small!r}
ITERS = 6 if SMALL else 30
WARM = 2 if SMALL else 5

def timeit(fn, *args, iters=ITERS, warmup=WARM):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters

case = {case!r}
rs = np.random.RandomState(0)
# each case times the Pallas kernel AND its stock-XLA twin at the same
# shape, so every knob row carries the ratio the bake-in rule needs
if case == "attn512":
    from singa_tpu.parallel.ring_attention import plain_attention
    B, H, S, D = (2, 4, 128, 64) if SMALL else (8, 12, 512, 64)
    q = jnp.asarray(rs.randn(B, H, S, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, H, S, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, H, S, D), jnp.float32)
    def step(attn, q, k, v):
        out, vjp = jax.vjp(lambda a, b, c: attn(a, b, c), q, k, v)
        return vjp(out)
    f = jax.jit(lambda q, k, v: step(
        lambda a, b, c: pk.flash_attention(a, b, c, True, None),
        q, k, v))
    f_ref = jax.jit(lambda q, k, v: step(
        lambda a, b, c: plain_attention(a, b, c, causal=True), q, k, v))
    us = timeit(f, q, k, v) * 1e6
    us_ref = timeit(f_ref, q, k, v) * 1e6
elif case == "dropout":
    n = 512 if SMALL else 4096
    x = jnp.asarray(rs.randn(n, n), jnp.float32)
    f = jax.jit(lambda x: pk.dropout(x, 0.3, jnp.int32(7)))
    key = jax.random.PRNGKey(7)
    def ref(x):
        m = jax.random.bernoulli(key, 0.7, x.shape).astype(x.dtype) / 0.7
        return x * m, m
    f_ref = jax.jit(ref)
    us = timeit(f, x) * 1e6
    us_ref = timeit(f_ref, x) * 1e6
elif case == "topk20":
    n = (1 << 14) if SMALL else (1 << 20)
    x = jnp.asarray(rs.randn(n), jnp.float32)
    f = jax.jit(lambda x: pk.topk_sparsify(x, 0.01))
    kk = int(n * 0.01)
    def ref(x):
        thr = jax.lax.top_k(jnp.abs(x), kk)[0][-1]
        return jnp.where(jnp.abs(x) >= thr, x, 0.0)
    f_ref = jax.jit(ref)
    us = timeit(f, x) * 1e6
    us_ref = timeit(f_ref, x) * 1e6
elif case == "xent1024":
    b = 128 if SMALL else 1024
    x = jnp.asarray(rs.randn(b, 1000), jnp.float32)
    lab = jnp.asarray(rs.randint(0, 1000, b), jnp.int32)
    def step(loss_fn, x):
        loss, vjp = jax.vjp(loss_fn, x)
        return vjp(1.0)
    f = jax.jit(lambda x: step(
        lambda a: jnp.sum(pk.softmax_xent(a, lab)), x))
    f_ref = jax.jit(lambda x: step(
        lambda a: jnp.sum(-jax.nn.log_softmax(a, -1)
                          [jnp.arange(b), lab]), x))
    us = timeit(f, x) * 1e6
    us_ref = timeit(f_ref, x) * 1e6
print("RESULT " + json.dumps(
    {{"case": case, "us": us, "us_ref": us_ref}}))
"""


def run_case(case, env_overrides, deadline=240, cpu=False,
             small=False):
    env = dict(os.environ)
    env.update({k: str(v) for k, v in env_overrides.items()})
    if cpu:
        env["PALLAS_TUNE_PLATFORM"] = "cpu"
    code = CASE_SRC.format(root=ROOT, case=case, small=small)
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True,
                             timeout=deadline)
    except subprocess.TimeoutExpired:
        return None
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            d = json.loads(line[len("RESULT "):])
            return d["us"], d["us_ref"]
    print(out.stderr[-400:], file=sys.stderr)
    return None


SWEEPS = [
    ("attn512", "SINGA_TPU_ATTN_TQ", [64, 128, 256, 512]),
    ("xent1024", "SINGA_TPU_ROW_BUDGET",
     [1 << 17, 1 << 18, 1 << 19, 1 << 20]),
    ("dropout", "SINGA_TPU_ROW_BUDGET",
     [1 << 17, 1 << 18, 1 << 19, 1 << 20, 1 << 21]),
    ("topk20", "SINGA_TPU_HIST_BUDGET",
     [1 << 11, 1 << 12, 1 << 13, 1 << 14, 1 << 15]),
]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--cpu", action="store_true",
                   help="force the jax CPU backend (Pallas interpret "
                   "mode, reduced shapes): chip-free RELATIVE "
                   "ranking for the autotuner; never a bake-in basis")
    p.add_argument("--jsonl", default="",
                   help="append one {case, knob, value, us, us_ref} "
                   "record per measurement — the score source "
                   "singa_tpu.tuning.ingest_pallas_jsonl reads")
    p.add_argument("--deadline", type=float, default=240.0,
                   help="per-measurement subprocess deadline")
    p.add_argument("--cases", default="",
                   help="comma-separated case subset (default: all)")
    args = p.parse_args(argv)

    sink = None
    if args.jsonl:
        d = os.path.dirname(args.jsonl)
        if d:
            os.makedirs(d, exist_ok=True)
        sink = open(args.jsonl, "a")
    only = set(c for c in args.cases.split(",") if c)
    mode = "cpu/interpret" if args.cpu else "on-chip"
    print(f"# pallas tune sweep ({time.strftime('%Y-%m-%d %H:%M')}, "
          f"{mode})")
    try:
        for case, knob, values in SWEEPS:
            if only and case not in only:
                continue
            rows = []
            for v in values:
                r = run_case(case, {knob: v},
                             deadline=args.deadline, cpu=args.cpu,
                             small=args.cpu)
                if r is None:
                    print(f"{case:10s} {knob}={v:<9} FAIL", flush=True)
                    continue
                us, us_ref = r
                rows.append((v, us, us_ref))
                print(f"{case:10s} {knob}={v:<9} {us:9.1f} us  "
                      f"(XLA {us_ref:9.1f} us, {us_ref / us:.2f}x)",
                      flush=True)
                if sink is not None:
                    sink.write(json.dumps({
                        "case": case, "knob": knob, "value": v,
                        "us": round(us, 3),
                        "us_ref": round(us_ref, 3),
                        "ratio": round(us_ref / us, 4),
                        "mode": mode,
                    }) + "\n")
                    sink.flush()
            if rows:
                v, us, us_ref = min(rows, key=lambda t: t[1])
                if args.cpu:
                    print(f"--> best {case}: {knob}={v} ({us:.1f} us "
                          "interpret-mode — ranking only, never a "
                          "bake-in basis)\n")
                else:
                    verdict = ("BAKE IT IN" if us_ref / us >= 1.1
                               else "stays below the 1.1x bake-in bar")
                    print(f"--> best {case}: {knob}={v} ({us:.1f} us, "
                          f"{us_ref / us:.2f}x XLA) — {verdict}\n")
    finally:
        if sink is not None:
            sink.close()


if __name__ == "__main__":
    main()
