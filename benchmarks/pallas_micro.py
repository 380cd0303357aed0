"""Microbenchmark: Pallas kernel tier vs stock-jnp lowering.

Reference context: the reference hand-writes CUDA kernels
(src/core/tensor/math_kernel.cu) where fused launches beat library
composition; this measures whether our Pallas equivalents
(singa_tpu/ops/pallas_kernels.py) do the same vs XLA's own fusion.

Run ON TPU:  python benchmarks/pallas_micro.py
             (writes/updates benchmarks/PALLAS_BENCH.md)
Off-TPU the kernels only run in interpret mode — timings would be
meaningless — so the script refuses unless --interpret is passed.
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..")))


def timeit(fn, *args, iters=50, warmup=5):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true",
                    help="allow running off-TPU (correctness only; "
                         "timings are NOT meaningful)")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes (mechanics check; use with "
                         "--interpret off-TPU)")
    ap.add_argument("--cpu", action="store_true",
                    help="pin the XLA CPU backend in-process (the "
                         "chip is never touched)")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        from jax.extend.backend import clear_backends

        clear_backends()
    import jax.numpy as jnp

    from singa_tpu.ops import pallas_kernels as pk

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.interpret:
        print("refusing: not on TPU (pass --interpret for a "
              "correctness-only run)", file=sys.stderr)
        sys.exit(2)

    pk.enable(True)
    rows = []
    rs = np.random.RandomState(0)

    # --- fused softmax-xent (fwd+bwd) vs jnp ------------------------------
    xent_shapes = ([(16, 64)] if args.small
                   else [(256, 1000), (1024, 1000), (256, 32000)])
    for b, c in xent_shapes:
        x = jnp.asarray(rs.randn(b, c).astype(np.float32))
        lab = jnp.asarray(rs.randint(0, c, b).astype(np.int32))

        f_pal = jax.jit(jax.value_and_grad(
            lambda x: jnp.mean(pk.softmax_xent(x, lab))))
        f_ref = jax.jit(jax.value_and_grad(
            lambda x: jnp.mean(-jax.nn.log_softmax(x, -1)[
                jnp.arange(b), lab])))
        (lp, gp) = f_pal(x)
        (lr, gr) = f_ref(x)
        err = float(jnp.max(jnp.abs(gp - gr)))
        t_pal = timeit(f_pal, x, iters=args.iters)
        t_ref = timeit(f_ref, x, iters=args.iters)
        rows.append((f"softmax_xent fwd+bwd {b}x{c}",
                     t_ref * 1e6, t_pal * 1e6, err))

    # --- top-K sparsification vs jax.lax.top_k ----------------------------
    for n in ([1 << 12] if args.small else [1 << 20, 1 << 24]):
        g = jnp.asarray(rs.randn(n).astype(np.float32))
        frac = 0.01
        k = int(n * frac)

        f_pal = jax.jit(lambda g: pk.topk_sparsify(g, frac))
        def ref(g):
            thr = jax.lax.top_k(jnp.abs(g), k)[0][-1]
            return jnp.where(jnp.abs(g) >= thr, g, 0.0)
        f_ref = jax.jit(ref)
        yp = f_pal(g)
        yr = f_ref(g)
        # pallas keeps >= k (histogram threshold); compare kept energy
        err = abs(float(jnp.sum(jnp.abs(yp)) / jnp.sum(jnp.abs(yr))) - 1)
        t_pal = timeit(f_pal, g, iters=max(5, args.iters // 5))
        t_ref = timeit(f_ref, g, iters=max(5, args.iters // 5))
        rows.append((f"topk_sparsify 1% of 2^{n.bit_length()-1}",
                     t_ref * 1e6, t_pal * 1e6, err))

    # --- fused (flash) attention vs XLA plain attention -------------------
    from singa_tpu.parallel.ring_attention import plain_attention

    attn_shapes = ([(1, 2, 128, 32)] if args.small
                   else [(8, 12, 512, 64), (4, 16, 1024, 64),
                         (2, 16, 2048, 128)])
    for b, h, s, d in attn_shapes:
        q = jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))
        k = jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))
        v = jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))

        f_pal = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(pk.flash_attention(q, k, v, True)),
            argnums=(0, 1, 2)))
        f_ref = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(plain_attention(q, k, v, causal=True)),
            argnums=(0, 1, 2)))
        gp, gr = f_pal(q, k, v), f_ref(q, k, v)
        err = max(float(jnp.max(jnp.abs(a - b_)))
                  for a, b_ in zip(gp, gr))
        it = max(3, args.iters // 10)
        t_pal = timeit(f_pal, q, k, v, iters=it)
        t_ref = timeit(f_ref, q, k, v, iters=it)
        rows.append((f"flash_attn fwd+bwd {b}x{h}x{s}x{d}",
                     t_ref * 1e6, t_pal * 1e6, err))

    # --- fused dropout vs jax.random (TPU only) ---------------------------
    if on_tpu:
        x = jnp.asarray(rs.randn(4096, 4096).astype(np.float32))
        key = jax.random.PRNGKey(0)
        f_pal = jax.jit(lambda x: pk.dropout(x, 0.3, 7)[0])
        f_ref = jax.jit(lambda x: x * (
            jax.random.bernoulli(key, 0.7, x.shape).astype(x.dtype)
            / 0.7))
        t_pal = timeit(f_pal, x, iters=args.iters)
        t_ref = timeit(f_ref, x, iters=args.iters)
        rows.append(("dropout 4096x4096", t_ref * 1e6, t_pal * 1e6, 0.0))

    backend = jax.default_backend()
    lines = [
        "# Pallas kernel microbenchmarks",
        "",
        f"Backend: `{backend}`"
        + ("" if on_tpu else "  — **interpret mode: timings not "
                             "meaningful, correctness columns only**"),
        "",
        "| kernel | jnp/XLA (us) | pallas (us) | speedup | max err |",
        "|---|---|---|---|---|",
    ]
    for name, t_ref, t_pal, err in rows:
        lines.append(f"| {name} | {t_ref:.1f} | {t_pal:.1f} | "
                     f"{t_ref / t_pal:.2f}x | {err:.2e} |")
    out = "\n".join(lines) + "\n"
    print(out)
    if on_tpu:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "PALLAS_BENCH.md")
        with open(path, "w") as f:
            f.write(out)
        print(f"wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
