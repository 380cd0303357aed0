"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls,
at the full width of the models the repo supports, in ONE process (a
chip belongs to one process at a time):

  trainer    ResNet-50 224x224 batch 128, bf16 AMP, SGD, graph mode
             (`device.create_tpu_device`, `Model.compile`, `model(x, y)`)
  server     GPT-2-small-width `TransformerLM` behind `ServingEngine`
             (`start` / `warm_decode` / `submit_decode`), greedy streams
             compared with `model.generate()`
  kernels    the Pallas tier compiled by Mosaic (never interpreted):
             softmax-xent and flash attention against the jnp path,
             then LM train steps with the tier on
  multichip  on >= 4 chips: the trainer data-parallel over four, and a
             decode engine on chip 2

Weights are random, made from a seed; depth and step counts are what is
cut, never a width. Every phase checks what came out — finite values,
expected shapes, agreement with a reference, every checked array on
the TPU — and the run fails if any phase failed. The times it prints
are observations of this run, named with the device kind, not
benchmark metrics.

There is no CPU mode: without a TPU the script exits non-zero before
it builds anything. The phases are plain functions of their sizes so
that `tests/test_tpu_smoke.py` can debug them at toy sizes on the CPU
mesh instead of on chip time.

The export cache and the MetricsLogger are not armed: nothing is
written under `.export_cache/` or `metrics/`. The persistent compile
cache is (`device.use_compile_cache`); the script says where it is and
how many entries it held before and after.

Last stdout line: one JSON object with exactly these keys,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}`,
the device as jax reports it. The line before it, `[smoke] result:
{...}`, is the full record: a result per phase, versions, the compile
cache's counts, wall time. Exit code 0 only if every phase passed.
"""
import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# `benchmarks.fleet_factory` and the CNN zoo's `resnet` module
sys.path[:0] = [HERE, os.path.join(HERE, "examples", "cnn", "model")]


class SmokeFailure(Exception):
    """A phase produced something wrong (not an `assert`: those vanish
    under `python -O`)."""


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _platforms(arr):
    return {d.platform for d in arr.devices()}


def _check_on(platform, what, arrays):
    """Every array lives on devices of `platform` only."""
    for i, a in enumerate(arrays):
        _check(_platforms(a) == {platform},
               f"{what}[{i}] lives on {sorted(_platforms(a))}, "
               f"expected {platform!r} only")


@contextlib.contextmanager
def _policies(matmul_precision, compute_dtype=None):
    """Set the process-wide numeric policies for one phase and put
    them back, so phases (and the tests that call them) stay
    independent."""
    from singa_tpu import tensor

    saved = (tensor.get_matmul_precision(), tensor.get_compute_dtype())
    tensor.set_matmul_precision(matmul_precision)
    tensor.set_compute_dtype(compute_dtype)
    try:
        yield
    finally:
        tensor.set_matmul_precision(saved[0])
        tensor.set_compute_dtype(saved[1])


class _CompileMeter:
    """Counts the executables jax builds (`compiles`: each one either
    compiled by XLA or loaded from the persistent cache) and how many
    of them the persistent cache served (`cache_hits`), through jax's
    own monitoring events. Listeners cannot be removed, so one meter
    serves the whole run."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------
def phase_trainer(depth=50, image=224, batch=128, steps=48,
                  data_parallel=0, platform="tpu"):
    """ResNet-`depth` bf16-AMP SGD train steps on one fixed batch,
    through `Model.compile(use_graph=True)` and `model(x, y)`.
    `data_parallel=n` compiles the same step over n devices
    (`ParallelPlan(data=n)` when the host has exactly n, else a
    n-device `mesh=`) and also checks the layout.

    48 steps, not 8: at lr 0.1 / momentum 0.9 without warm-up
    ResNet-50 first overshoots — on the v5e the loss went 8.1 -> 5.6
    -> 9.9 and was back under its first value only from step 15,
    falling steadily after step 20 — and a step is ~55 ms."""
    import jax
    import resnet

    from singa_tpu import device, opt, tensor
    from singa_tpu.parallel import ParallelPlan, create_mesh

    with _policies("default", "bfloat16"):
        dev = device.create_tpu_device()
        dev.SetRandSeed(0)
        m = resnet.create_model(depth=depth)
        sgd = opt.SGD(lr=0.1, momentum=0.9)
        m.set_optimizer(sgd)
        rs = np.random.RandomState(0)
        tx = tensor.from_numpy(
            rs.randn(batch, 3, image, image).astype(np.float32),
            device=dev)
        ty = tensor.from_numpy(
            rs.randint(0, 1000, batch).astype(np.int32), device=dev)
        layout = {}
        if data_parallel:
            if jax.device_count() == data_parallel:
                layout["plan"] = ParallelPlan(data=data_parallel)
            else:
                layout["mesh"] = create_mesh(
                    {"data": data_parallel},
                    jax.devices()[:data_parallel])
        m.compile([tx], is_train=True, use_graph=True, **layout)

        losses, times = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            out, loss = m(tx, ty)
            loss.data.block_until_ready()
            times.append(time.perf_counter() - t0)
            losses.append(float(loss.to_numpy()))
        _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
        _check(losses[-1] < losses[0],
               f"loss did not fall over {steps} steps: {losses}")
        _check(out.shape == (batch, 1000), f"logits shape {out.shape}")
        params = [p.data for p in m.param_tensors()]
        slots = [a for st in sgd.states.values() for a in st.values()]
        _check(slots, "optimizer holds no slots")
        _check_on(platform, "param", params)
        _check_on(platform, "state", [s.data for s in m.state_tensors()])
        _check_on(platform, "optimizer slot", slots)
        _check_on(platform, "loss", [loss.data])
        res = {"ok": True, "steps": steps,
               "loss_first": round(losses[0], 4),
               "loss_last": round(losses[-1], 4),
               "first_step_s": round(times[0], 2),
               "second_step_s": round(times[1], 2),
               "steady_step_ms": round(
                   1e3 * float(np.median(times[2:])), 2)}
        if data_parallel:
            on = {len(p.devices()) for p in params}
            _check(on == {data_parallel},
                   f"params addressable on {sorted(on)} devices, "
                   f"expected {data_parallel}")
            # the step's per-example output carries the batch layout
            rows = [s.data.shape[0] for s in out.data.addressable_shards]
            _check(len(out.data.devices()) == data_parallel
                   and rows == [batch // data_parallel] * data_parallel,
                   f"batch sharded as {rows} rows over "
                   f"{len(out.data.devices())} devices")
            res["data_parallel"] = data_parallel
        return res


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------
def _logits_at(m, seq, t, bucket, precision):
    """Next-token logits after `seq[:t]`, through the model's own
    bucket-padded prefill program, under matmul policy `precision`."""
    import jax.numpy as jnp

    params = m._decode_params()
    heads = m.blocks._seq[0].attn.num_heads
    embed = params["embed"]
    cache = jnp.zeros(
        (len(params["blocks"]), 2, 1, heads, bucket,
         embed.shape[-1] // heads), embed.dtype,
        device=next(iter(embed.devices())))
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :t] = seq[:t]
    with _policies(precision):
        logits, _ = m.prefill_step(params, cache, ids, jnp.int32(t))
    return np.asarray(logits)[0]


def phase_server(vocab=50257, d_model=768, num_heads=12, num_layers=12,
                 max_len=1024, sessions=8, prompt_len=128, new_tokens=64,
                 requests=8, device_index=0, platform="tpu", meter=None):
    """A `TransformerLM` built as `fleet_factory.create_lm` builds it,
    behind `ServingEngine(max_sessions=sessions)`: warm the decode
    ladder, answer `requests` greedy sessions, reconcile the counters,
    check placement, and compare every stream with `generate()`."""
    from benchmarks import fleet_factory
    from singa_tpu import device, serve, stats

    # create_lm sets the matmul policy to "default" (bf16 MXU passes)
    with _policies("default"):
        t0 = time.perf_counter()
        m = fleet_factory.create_lm(
            vocab=vocab, d_model=d_model, num_heads=num_heads,
            num_layers=num_layers, max_len=max_len, seed=0,
            device_index=device_index)
        build_s = time.perf_counter() - t0
        want_dev = device.create_replica_device(device_index).jax_device
        rs = np.random.RandomState(1)
        prompts = [rs.randint(0, vocab, prompt_len).astype(np.int32)
                   for _ in range(requests)]
        before = stats.cache_stats()["decode"]
        eng = serve.ServingEngine(m, max_sessions=sessions).start()
        try:
            t0 = time.perf_counter()
            warmed = eng.warm_decode(prompt_lens=[prompt_len],
                                     max_new_tokens=new_tokens)
            warm_s = time.perf_counter() - t0
            compiles0 = meter.compiles if meter else 0
            t0 = time.perf_counter()
            replies = [eng.submit_decode(p, new_tokens) for p in prompts]
            got = [np.asarray(r.result(timeout=600)) for r in replies]
            serve_s = time.perf_counter() - t0
            compiles_in_window = (meter.compiles - compiles0
                                  if meter else None)
            slab = [a for layer in eng._slab for a in
                    (layer if isinstance(layer, tuple) else (layer,))]
        finally:
            eng.stop()
        after = stats.cache_stats()["decode"]
        dd = {k: after[k] - before[k] for k in
              ("sessions", "completed", "failed", "expired", "shed",
               "tokens_streamed")}

        for p, g in zip(prompts, got):
            _check(g.shape == (1, prompt_len + new_tokens),
                   f"reply shape {g.shape}")
            _check(np.array_equal(g[0, :prompt_len], p),
                   "reply does not start with its prompt")
            new = g[0, prompt_len:]
            _check(new.min() >= 0 and new.max() < vocab,
                   f"token out of range: [{new.min()}, {new.max()}]")
        _check(dd["sessions"] == dd["completed"] == requests
               and dd["failed"] == dd["expired"] == dd["shed"] == 0
               and dd["tokens_streamed"] == requests * new_tokens,
               f"decode counters do not reconcile: {dd}")
        params = [p.data for p in m.param_tensors()]
        _check_on(platform, "param", params)
        _check_on(platform, "slab", slab)
        for what, arrays in (("param", params), ("slab", slab)):
            off = [i for i, a in enumerate(arrays)
                   if a.devices() != {want_dev}]
            _check(not off, f"{what} {off[:4]} not on {want_dev} "
                            f"(replica device {device_index})")
        _check(compiles_in_window in (None, 0),
               f"{compiles_in_window} executable(s) built inside the "
               "request window: warm_decode did not warm what the "
               "live path dispatches")

        # Greedy streams against generate(). Identity is what the
        # README promises and what XLA:CPU gives; on the MXU the fused
        # slab step (batch = slots) and generate() (batch 1) are
        # different programs whose matmuls round differently, so a
        # near-tie between the top two logits may resolve the other
        # way. A stream may differ ONLY at such a tie: the two tokens'
        # logits — recomputed at fp32 precision — must be no further
        # apart than the arithmetic's own noise.
        t0 = time.perf_counter()
        want = [m.generate(p[None], new_tokens) for p in prompts]
        generate_s = time.perf_counter() - t0
        bucket = 1 << (prompt_len + new_tokens - 1).bit_length()
        diverged = []
        for i, (g, w) in enumerate(zip(got, want)):
            g, w = g[0], np.asarray(w)[0]
            if np.array_equal(g, w):
                continue
            t = int(np.argmax(g != w))
            exact = _logits_at(m, w, t, bucket, "highest")
            noise = float(np.max(np.abs(
                _logits_at(m, w, t, bucket, "default") - exact)))
            top2 = np.sort(exact)[-2:]
            d = {"stream": i, "position": t,
                 "new_token_index": t - prompt_len,
                 "served": int(g[t]), "generated": int(w[t]),
                 "logit_gap_between_them": round(
                     float(abs(exact[g[t]] - exact[w[t]])), 6),
                 "top2_margin": round(float(top2[1] - top2[0]), 6),
                 "matmul_noise": round(noise, 6)}
            print(f"[smoke] stream {i} differs from generate(): {d}",
                  flush=True)
            diverged.append(d)
        for d in diverged:
            _check(d["logit_gap_between_them"] <= 2 * d["matmul_noise"],
                   "stream differs from generate() beyond float "
                   f"tolerance: {d}")
        return {"ok": True, "requests": requests,
                "new_tokens_each": new_tokens, "warmed": warmed,
                "decode": dd, "device": str(want_dev),
                "streams_identical_to_generate":
                    requests - len(diverged),
                "streams_diverged_at_a_near_tie": diverged,
                "compiles_in_request_window": compiles_in_window,
                "build_s": round(build_s, 2),
                "warm_decode_s": round(warm_s, 2),
                "requests_s": round(serve_s, 2),
                "generate_s": round(generate_s, 2)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
# Parity with the jnp path. Softmax-xent: elementwise, the tolerances
# tests/test_pallas.py uses. Attention: the tests' rtol (1e-4 for
# float32, where the kernel runs its dots at fp32 precision under the
# framework's default matmul policy; two bf16 units in the last place
# for bfloat16), applied to the tensor's SCALE — at S=1024 an fp32
# reduction's own rounding (1.2e-4 measured on the v5e, on gradients
# up to 7) passes the tests' atol of 1e-5 on near-zero elements,
# which the S<=192 CPU tests never see. A kernel that fell back to
# one bf16 pass is 2e-2 off and still fails by a factor of 30.
_XENT_TOL = (1e-5, 1e-6)
_ATTN_TOL = {"float32": 1e-4, "bfloat16": 2 ** -6}


def _finite32(what, got):
    got = np.asarray(got, np.float32)
    _check(np.all(np.isfinite(got)), f"{what}: non-finite values")
    return got


def _close(what, got, ref, rtol, atol):
    """Elementwise |got - ref| <= atol + rtol*|ref|."""
    got, ref = _finite32(what, got), np.asarray(ref, np.float32)
    err = np.abs(got - ref)
    bad = err > atol + rtol * np.abs(ref)
    _check(not bad.any(),
           f"{what}: {int(bad.sum())} of {bad.size} elements off the "
           f"jnp path (max abs err {float(err.max()):.3e}, rtol {rtol}, "
           f"atol {atol})")
    return float(err.max())


def _close_at_scale(what, got, ref, tol):
    """max|got - ref| <= tol * max|ref|."""
    got, ref = _finite32(what, got), np.asarray(ref, np.float32)
    err, scale = float(np.abs(got - ref).max()), float(np.abs(ref).max())
    _check(err <= tol * scale,
           f"{what}: max abs err {err:.3e} is {err / scale:.2e} of the "
           f"jnp path's scale {scale:.3g} (tolerance {tol:.1e})")
    return err


def _kernel_xent(b, c):
    import jax
    import jax.numpy as jnp

    from singa_tpu.ops import pallas_kernels as pk

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(b, c).astype(np.float32))
    lab = jnp.asarray(rs.randint(0, c, b).astype(np.int32))
    fused = jax.jit(jax.value_and_grad(
        lambda x: jnp.sum(pk.softmax_xent(x, lab))))
    plain = jax.jit(jax.value_and_grad(lambda x: jnp.sum(
        -jax.nn.log_softmax(x, -1)[jnp.arange(b), lab])))
    (lf, gf), (lp, gp) = fused(x), plain(x)
    _close(f"xent {b}x{c} loss", lf / b, lp / b, *_XENT_TOL)
    return _close(f"xent {b}x{c} grad", gf, gp, *_XENT_TOL)


def _kernel_attention(b, h, s, d, dtype):
    import jax
    import jax.numpy as jnp

    from singa_tpu.ops import pallas_kernels as pk
    from singa_tpu.parallel.ring_attention import plain_attention

    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))
               .astype(dtype) for _ in range(3))

    def loss(attn):
        def f(q, k, v):
            o = attn(q, k, v).astype(jnp.float32)
            return jnp.sum(jnp.sin(o)), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    fused = loss(lambda q, k, v: pk.flash_attention(
        q, k, v, True, None, "highest"))
    plain = loss(lambda q, k, v: plain_attention(
        *(a.astype(jnp.float32) for a in (q, k, v)), causal=True,
        precision="highest"))
    ((_, of), gf), ((_, op), gp) = fused(q, k, v), plain(q, k, v)
    tol = _ATTN_TOL[dtype]
    tag = f"attention {b}x{h}x{s}x{d} {dtype}"
    errs = [_close_at_scale(f"{tag} out", of, op, tol)]
    for name, a, r in zip("qkv", gf, gp):
        errs.append(_close_at_scale(f"{tag} d{name}", a, r, tol))
    return max(errs)


def _kernel_decode_attend(b, h, d, t):
    """One query a row against the blocks that row has written, rows
    of every length in one call, against the two `einsum`s over the
    rung at fp32 precision."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.models.transformer import rung_attend
    from singa_tpu.ops import pallas_kernels as pk

    rs = np.random.RandomState(0)
    layer = jnp.asarray(rs.randn(2, b, h, d, t).astype(np.float32))
    q = jnp.asarray(rs.randn(b, h, d).astype(np.float32))
    pos = rs.randint(0, t, b).astype(np.int32)
    pos[:4] = (0, 127, 128, t - 1)
    pos = jnp.asarray(pos)
    scale = 1.0 / float(np.sqrt(d))
    got = jax.jit(lambda l, q, p: pk.decode_attend(l, q, p, scale))(
        layer, q, pos)
    want = jax.jit(lambda l, q, p: rung_attend(l, q, p, scale, "highest"))(
        layer, q, pos)
    return _close(f"decode_attend {b}x{h}x{d}x{t}", got, want, 1e-5, 1e-5)


def phase_kernels(xent_shapes=((128, 1000), (8192, 32000)),
                  attn_cases=((8, 8, 1024, 64, "bfloat16"),
                              (8, 8, 1024, 64, "float32"),
                              (2, 12, 1024, 64, "float32")),
                  decode_cases=((32, 12, 64, 1024), (64, 12, 64, 256)),
                  lm=(32000, 512, 8, 8), lm_batch=8, lm_seq=1024,
                  lm_steps=3, interpret=False, platform="tpu"):
    """The Pallas tier, compiled: each kernel fwd+bwd against the jnp
    path, then `lm_steps` train steps of a `TransformerLM` (vocab,
    d_model, heads, layers = `lm`) with the tier on, whose lowered
    step must hold the
    Mosaic custom calls — so the kernels ran, not a reference."""
    from singa_tpu import device, opt, tensor
    from singa_tpu.models.transformer import TransformerLM
    from singa_tpu.ops import pallas_kernels as pk

    was_enabled = pk.enabled()
    pk.enable(True)
    try:
        _check(pk._interpret() is interpret,
               f"pallas_kernels._interpret() is {pk._interpret()}, "
               f"expected {interpret}")
        res = {"ok": True, "max_abs_err": {}}
        for b, c in xent_shapes:
            res["max_abs_err"][f"xent_{b}x{c}"] = _kernel_xent(b, c)
        for b, h, s, d, dtype in attn_cases:
            res["max_abs_err"][f"attn_{b}x{h}x{s}x{d}_{dtype}"] = \
                _kernel_attention(b, h, s, d, dtype)
        for b, h, d, t in decode_cases:
            res["max_abs_err"][f"decode_attend_{b}x{h}x{d}x{t}"] = \
                _kernel_decode_attend(b, h, d, t)

        vocab, d_model, heads, layers = lm
        _check(pk.attn_supported(lm_seq, d_model // heads),
               f"flash attention does not engage at seq {lm_seq}")
        with _policies("default", "bfloat16"):
            dev = device.create_tpu_device()
            dev.SetRandSeed(0)
            m = TransformerLM(vocab, d_model=d_model, num_heads=heads,
                              num_layers=layers, max_len=lm_seq)
            m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
            rs = np.random.RandomState(0)
            tx, ty = (tensor.from_numpy(
                rs.randint(0, vocab, (lm_batch, lm_seq))
                .astype(np.int32), device=dev) for _ in range(2))
            m.compile([tx], is_train=True, use_graph=True)
            losses, times = [], []
            for _ in range(lm_steps):
                t0 = time.perf_counter()
                _, loss = m(tx, ty)
                loss.data.block_until_ready()
                times.append(time.perf_counter() - t0)
                losses.append(float(loss.to_numpy()))
            _check(all(np.isfinite(losses)),
                   f"non-finite LM loss: {losses}")
            _check(losses[-1] < losses[0],
                   f"LM loss did not fall: {losses}")
            _check_on(platform, "LM param",
                      [p.data for p in m.param_tensors()])
            _check_on(platform, "LM loss", [loss.data])
            if not interpret:
                calls = m.step_hlo_text(tx, ty, optimized=False) \
                    .count("tpu_custom_call")
                # xent fwd+bwd, and per layer attention fwd, dq, dk/dv
                _check(calls >= 2 + 3 * layers,
                       f"{calls} Mosaic custom calls in the LM step, "
                       f"expected >= {2 + 3 * layers}")
                res["lm_mosaic_custom_calls"] = calls
        res.update(lm_loss_first=round(losses[0], 4),
                   lm_loss_last=round(losses[-1], 4),
                   lm_first_step_s=round(times[0], 2),
                   lm_last_step_ms=round(1e3 * times[-1], 2))
        return res
    finally:
        pk.enable(was_enabled)


# ---------------------------------------------------------------------------
# multichip
# ---------------------------------------------------------------------------
def phase_multichip(chips=4, trainer=None, server=None, platform="tpu",
                    meter=None):
    """On a host with >= `chips` devices: the trainer phase again,
    data-parallel over `chips` (global batch 512), and one decode
    engine on chip 2 (params AND slab there). On fewer devices the
    phase is skipped for lack of hardware — a skip, not a caught
    failure."""
    import jax

    n = jax.device_count()
    if n < chips:
        return {"ok": True, "skipped": f"{n} device(s)"}
    tr = phase_trainer(**{"batch": 512, "data_parallel": chips,
                          "platform": platform, **(trainer or {})})
    sv = phase_server(**{"requests": 1, "device_index": 2,
                         "platform": platform, "meter": meter,
                         **(server or {})})
    return {"ok": True, "devices": n, "trainer": tr, "server": sv}


# ---------------------------------------------------------------------------
def _report(ok, dev_info, **detail):
    """The run's two closing stdout lines: the full record, then the
    verdict — `ok` and the device, nothing else — as the LAST line."""
    print("[smoke] result: " + json.dumps(
        {"ok": ok, "device": dev_info, **detail}), flush=True)
    print(json.dumps({"ok": ok, "device": dev_info}), flush=True)


def main():
    t_start = time.time()
    import jax
    import jaxlib

    from singa_tpu import device, tuning

    d = jax.devices()[0]
    dev_info = {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        import libtpu

        versions["libtpu"] = libtpu.__version__
    except ImportError:
        pass
    print(f"[smoke] device: {dev_info}  versions: {versions}",
          flush=True)
    if d.platform != "tpu":
        print(f"[smoke] no TPU: jax found platform {d.platform!r}; "
              "chip_smoke.py has no CPU mode", file=sys.stderr)
        return 2
    tuning.normalize_chip(d.device_kind)  # unknown kind: ValueError

    cache_dir = device.use_compile_cache()

    def entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
            else 0

    entries0 = entries()
    print(f"[smoke] compile cache: {cache_dir} "
          f"({entries0} entries before)", flush=True)
    meter = _CompileMeter()

    phases = {}
    for name, run in (("trainer", phase_trainer),
                      ("server", lambda: phase_server(meter=meter)),
                      ("kernels", phase_kernels),
                      ("multichip",
                       lambda: phase_multichip(meter=meter))):
        t0 = time.time()
        try:
            phases[name] = run()
        except Exception as e:  # recorded, reported, and the run FAILS
            traceback.print_exc()
            phases[name] = {"ok": False,
                            "error": f"{type(e).__name__}: {e}"[:2000]}
        phases[name]["wall_s"] = round(time.time() - t0, 1)
        print(f"[smoke] {name}: {json.dumps(phases[name])}", flush=True)

    ok = all(p["ok"] for p in phases.values())
    _report(ok, dev_info, versions=versions, phases=phases,
            observed_on=d.device_kind,
            compile_cache={"dir": cache_dir, "entries_before": entries0,
                           "entries_after": entries(),
                           "executables_built": meter.compiles,
                           "of_them_from_cache": meter.cache_hits},
            wall_s=round(time.time() - t_start, 1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
