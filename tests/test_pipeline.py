"""Pipeline-parallel tests (singa_tpu/parallel/pipeline.py).

The reference has no pipeline parallelism (SURVEY.md §2.4); these
assert the GPipe schedule is EXACT — forward outputs and per-stage
parameter gradients equal the plain sequential composition — on the
8-virtual-device CPU mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from singa_tpu.parallel import (
    pipeline_apply,
    place_stacked,
    stack_stage_params,
)


def _mlp_stage(p, h):
    return jax.nn.gelu(h @ p["W"] + p["b"]) + h


def _stages(n, d, seed=0):
    rs = np.random.RandomState(seed)
    return [{"W": jnp.asarray(rs.randn(d, d).astype(np.float32) * 0.2),
             "b": jnp.asarray(rs.randn(d).astype(np.float32) * 0.1)}
            for _ in range(n)]


def _ref(stages, x, fn=_mlp_stage):
    h = x
    for p in stages:
        h = fn(p, h)
    return h


@pytest.fixture
def mesh4():
    return Mesh(np.array(jax.devices()[:4]).reshape(4), ("pipe",))


@pytest.mark.parametrize("microbatches", [4, 8])
def test_forward_matches_sequential(mesh4, microbatches):
    per_stage = _stages(4, 16)
    x = jnp.asarray(
        np.random.RandomState(1).randn(8, 16).astype(np.float32))
    stacked = place_stacked(stack_stage_params(per_stage), mesh4)
    y = pipeline_apply(_mlp_stage, stacked, x, mesh4,
                       microbatches=microbatches)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_ref(per_stage, x)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_grads_match_sequential(mesh4):
    per_stage = _stages(4, 16, seed=2)
    x = jnp.asarray(
        np.random.RandomState(3).randn(8, 16).astype(np.float32))
    stacked = place_stacked(stack_stage_params(per_stage), mesh4)

    def loss_pp(params):
        return jnp.sum(jnp.sin(
            pipeline_apply(_mlp_stage, params, x, mesh4,
                           microbatches=4)))

    def loss_ref(stages):
        return jnp.sum(jnp.sin(_ref(stages, x)))

    g_pp = jax.grad(loss_pp)(stacked)
    g_ref = stack_stage_params(jax.grad(loss_ref)(per_stage))
    for k in ("W", "b"):
        np.testing.assert_allclose(np.asarray(g_pp[k]),
                                   np.asarray(g_ref[k]),
                                   rtol=1e-4, atol=1e-5)


def test_transformer_block_pipeline(mesh4):
    """Pipelined pre-LN attention+FFN blocks (the real workload shape:
    [B, S, D] activations)."""
    d, heads = 16, 2

    def block(p, h):
        # pre-LN MHSA (single fused head math, causal-free)
        mu = h.mean(-1, keepdims=True)
        sd = jnp.sqrt(((h - mu) ** 2).mean(-1, keepdims=True) + 1e-5)
        hn = (h - mu) / sd
        b_, s_, _ = h.shape
        q = (hn @ p["Wq"]).reshape(b_, s_, heads, d // heads)
        k = (hn @ p["Wk"]).reshape(b_, s_, heads, d // heads)
        v = (hn @ p["Wv"]).reshape(b_, s_, heads, d // heads)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d // heads)
        a = jax.nn.softmax(sc, -1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b_, s_, d)
        h = h + ctx @ p["Wo"]
        return h + jax.nn.gelu(h @ p["Wf"]) @ p["Wp"]

    rs = np.random.RandomState(4)

    def mk():
        s = lambda *sh: jnp.asarray(  # noqa: E731
            rs.randn(*sh).astype(np.float32) * 0.2)
        return {"Wq": s(d, d), "Wk": s(d, d), "Wv": s(d, d),
                "Wo": s(d, d), "Wf": s(d, 2 * d), "Wp": s(2 * d, d)}

    per_stage = [mk() for _ in range(4)]
    x = jnp.asarray(rs.randn(4, 8, d).astype(np.float32))
    stacked = place_stacked(stack_stage_params(per_stage), mesh4)
    y = jax.jit(lambda p, x: pipeline_apply(block, p, x, mesh4,
                                            microbatches=4))(stacked, x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_ref(per_stage, x, block)),
                               rtol=1e-4, atol=1e-5)


def test_batch_not_divisible_raises_loud_valueerror(mesh4):
    """ISSUE 10 satellite: indivisible batches raise the
    `data.microbatches` splitter's loud ValueError (naming batch size
    and microbatch count, plus the pipeline's shape context) instead
    of the former bare assert."""
    per_stage = _stages(4, 8)
    x = jnp.zeros((6, 8), jnp.float32)
    stacked = place_stacked(stack_stage_params(per_stage), mesh4)
    with pytest.raises(ValueError) as ei:
        pipeline_apply(_mlp_stage, stacked, x, mesh4, microbatches=4)
    msg = str(ei.value)
    assert "(6, 8)" in msg and "microbatches=4" in msg
    assert "not divisible" in msg


def test_pad_routes_through_splitter(mesh4):
    """`pad=True` repeat-pads the tail (the `data.microbatches` pad
    contract) and slices the pad rows back off the output."""
    per_stage = _stages(4, 8, seed=6)
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(6, 8).astype(np.float32))
    stacked = place_stacked(stack_stage_params(per_stage), mesh4)
    y = pipeline_apply(_mlp_stage, stacked, x, mesh4, microbatches=4,
                       pad=True)
    assert y.shape == (6, 8)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_ref(per_stage, x)),
                               rtol=1e-5, atol=1e-6)


def test_microbatches_default_is_pipe_size(mesh4):
    from singa_tpu import stats

    per_stage = _stages(4, 8)
    x = jnp.zeros((8, 8), jnp.float32)
    stacked = place_stacked(stack_stage_params(per_stage), mesh4)
    pipeline_apply(_mlp_stage, stacked, x, mesh4)
    note = stats.cache_stats()["parallel"]["pipeline"]
    assert note["microbatches"] == 4 and note["stages"] == 4


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
@pytest.mark.parametrize("microbatches", [4, 8])
def test_build_note_states_the_schedules_geometry(mesh4, microbatches,
                                                  schedule):
    """`cache_stats()["parallel"]["pipeline"]` after a build: the
    analytic bubble (P-1)/(M+P-1) and the tick count, doubled where
    1F1B runs forward and backward in one pass. (A measured bubble is
    a device time and comes from a chip run, never from here.)"""
    from singa_tpu import stats

    P, M = 4, microbatches
    stacked = place_stacked(stack_stage_params(_stages(P, 8)), mesh4)
    pipeline_apply(_mlp_stage, stacked, jnp.zeros((8, 8), jnp.float32),
                   mesh4, microbatches=M, schedule=schedule)
    assert stats.cache_stats()["parallel"]["pipeline"] == {
        "stages": P, "microbatches": M, "schedule": schedule,
        "bubble_ticks": P - 1,
        "ticks": (M + P - 1) * (2 if schedule == "1f1b" else 1),
        "bubble_fraction": round((P - 1) / (M + P - 1), 6)}


def test_unknown_schedule_raises(mesh4):
    stacked = place_stacked(stack_stage_params(_stages(4, 8)), mesh4)
    with pytest.raises(ValueError, match="schedule"):
        pipeline_apply(_mlp_stage, stacked, jnp.zeros((8, 8)), mesh4,
                       schedule="interleaved")


def test_bad_stacked_leading_dim_raises(mesh4):
    # host arrays: a 3-stage stack cannot even device_put onto a
    # 4-chip pipe axis, and the apply must refuse it loudly
    stacked = stack_stage_params(_stages(3, 8))
    with pytest.raises(ValueError, match="leading dim 3"):
        pipeline_apply(_mlp_stage, stacked, jnp.zeros((8, 8)), mesh4)


# ---------------------------------------------------------------------------
# 1F1B schedule (ISSUE 10 tentpole)
# ---------------------------------------------------------------------------
class TestOneFOneB:
    @pytest.mark.parametrize("microbatches", [4, 8])
    def test_forward_matches_sequential(self, mesh4, microbatches):
        per_stage = _stages(4, 16)
        x = jnp.asarray(
            np.random.RandomState(1).randn(8, 16).astype(np.float32))
        stacked = place_stacked(stack_stage_params(per_stage), mesh4)
        y = pipeline_apply(_mlp_stage, stacked, x, mesh4,
                           microbatches=microbatches, schedule="1f1b")
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_ref(per_stage, x)),
                                   rtol=1e-5, atol=1e-6)

    def test_grads_match_gpipe_and_sequential(self, mesh4):
        """1F1B-vs-GPipe loss/grad equivalence: the combined-schedule
        custom vjp computes the same gradients as reverse-mode through
        the forward scan, and both match the plain composition."""
        per_stage = _stages(4, 16, seed=2)
        x = jnp.asarray(
            np.random.RandomState(3).randn(8, 16).astype(np.float32))
        stacked = place_stacked(stack_stage_params(per_stage), mesh4)

        def loss(schedule):
            def f(params):
                return jnp.sum(jnp.sin(pipeline_apply(
                    _mlp_stage, params, x, mesh4, microbatches=4,
                    schedule=schedule)))
            return f

        g_1f1b = jax.grad(loss("1f1b"))(stacked)
        g_gpipe = jax.grad(loss("gpipe"))(stacked)
        g_ref = stack_stage_params(
            jax.grad(lambda s: jnp.sum(jnp.sin(_ref(s, x))))(per_stage))
        for k in ("W", "b"):
            np.testing.assert_allclose(np.asarray(g_1f1b[k]),
                                       np.asarray(g_gpipe[k]),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(g_1f1b[k]),
                                       np.asarray(g_ref[k]),
                                       rtol=1e-4, atol=1e-5)

    def test_input_grads_match_sequential(self, mesh4):
        per_stage = _stages(4, 16, seed=4)
        x = jnp.asarray(
            np.random.RandomState(5).randn(8, 16).astype(np.float32))
        stacked = place_stacked(stack_stage_params(per_stage), mesh4)
        gx = jax.grad(lambda xx: jnp.sum(jnp.sin(pipeline_apply(
            _mlp_stage, stacked, xx, mesh4, microbatches=4,
            schedule="1f1b"))))(x)
        gx_ref = jax.grad(
            lambda xx: jnp.sum(jnp.sin(_ref(per_stage, xx))))(x)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    def test_dp_pipe_grads_match(self):
        """dp x pipe composition: batch sharded over "data", grads
        psum-reduced over the replicas — equal to the sequential
        composition over the full batch."""
        per_stage = _stages(4, 16, seed=8)
        x = jnp.asarray(
            np.random.RandomState(9).randn(8, 16).astype(np.float32))
        mesh8 = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                     ("data", "pipe"))
        stacked = place_stacked(stack_stage_params(per_stage), mesh8)
        g = jax.grad(lambda p: jnp.sum(jnp.sin(pipeline_apply(
            _mlp_stage, p, x, mesh8, microbatches=2, schedule="1f1b",
            batch_axis="data"))))(stacked)
        g_ref = stack_stage_params(
            jax.grad(lambda s: jnp.sum(jnp.sin(_ref(s, x))))(per_stage))
        for k in ("W", "b"):
            np.testing.assert_allclose(np.asarray(g[k]),
                                       np.asarray(g_ref[k]),
                                       rtol=1e-4, atol=1e-5)

    def test_peak_bytes_strictly_below_gpipe_at_2p(self, mesh4):
        """THE liveness acceptance pin (ISSUE 10): at M >= 2P, the
        1F1B schedule's pre-optimization peak live bytes are STRICTLY
        below GPipe's — reverse-mode through the forward scan stashes
        residuals for all M microbatches per stage, while the 1F1B
        custom vjp's fwd->bwd boundary carries only params + inputs
        and its combined scan bounds in-flight activations by the
        P-slot ring buffer."""
        from singa_tpu import hlo_profile

        d, mb, M = 64, 64, 8  # M = 2P on the 4-stage mesh
        stacked = stack_stage_params(_stages(4, d, seed=5))
        x = jnp.zeros((mb * M, d), jnp.float32)

        def peak(schedule):
            f = jax.jit(jax.grad(lambda p, xx: jnp.sum(
                pipeline_apply(_mlp_stage, p, xx, mesh4,
                               microbatches=M,
                               schedule=schedule) ** 2)))
            txt = f.lower(stacked, x).as_text(dialect="hlo")
            return hlo_profile.peak_bytes_estimate(txt)

        p_1f1b, p_gpipe = peak("1f1b"), peak("gpipe")
        assert p_1f1b < p_gpipe, (
            f"1F1B peak {p_1f1b} not strictly below GPipe "
            f"{p_gpipe} at M=2P")
