"""Pallas kernel tier (reference: src/core/tensor/math_kernel.cu,
SURVEY.md N10/§7 — the hand-written kernels for fused/odd ops).

Kernels run in Pallas interpret mode on the CPU backend, so this suite
covers the kernel code paths without hardware; on a TPU the same calls
compile to Mosaic. Parity tolerance vs the stock-jnp paths: <= 1e-5
(VERDICT r1 next-round #4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import autograd, tensor
from singa_tpu.ops import pallas_kernels as pk


@pytest.fixture(autouse=True)
def _enable_pallas():
    pk.enable(True)
    yield
    pk.enable(False)


class TestSoftmaxXent:
    def test_forward_parity(self):
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(33, 17).astype(np.float32))
        lab = jnp.asarray(rs.randint(0, 17, 33).astype(np.int32))
        got = pk.softmax_xent(x, lab)
        want = -jax.nn.log_softmax(x, -1)[jnp.arange(33), lab]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_backward_parity(self):
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(16, 10).astype(np.float32))
        lab = jnp.asarray(rs.randint(0, 10, 16).astype(np.int32))

        def f_pallas(x):
            return jnp.mean(pk.softmax_xent(x, lab))

        def f_ref(x):
            return jnp.mean(
                -jax.nn.log_softmax(x, -1)[jnp.arange(16), lab])

        np.testing.assert_allclose(jax.grad(f_pallas)(x),
                                   jax.grad(f_ref)(x),
                                   rtol=1e-5, atol=1e-6)

    def test_autograd_op_uses_kernel_and_matches(self):
        """autograd.SoftMaxCrossEntropy with the flag on must agree
        with the flag off (the jnp path) in loss AND input grad."""
        rs = np.random.RandomState(2)
        x_np = rs.randn(12, 5).astype(np.float32)
        t_np = rs.randint(0, 5, 12).astype(np.int32)

        def run():
            x = tensor.from_numpy(x_np)
            x.requires_grad = True
            x.stores_grad = True
            t = tensor.from_numpy(t_np)
            loss = autograd.softmax_cross_entropy(x, t)
            grads = autograd.gradients(loss)
            return float(loss.to_numpy()), grads[x].to_numpy()

        l_pallas, g_pallas = run()
        pk.enable(False)
        l_ref, g_ref = run()
        assert abs(l_pallas - l_ref) <= 1e-5
        np.testing.assert_allclose(g_pallas, g_ref, rtol=1e-5, atol=1e-6)

    def test_jit_graph_mode(self):
        """The kernel must trace into a jitted program (graph mode)."""
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(8, 6).astype(np.float32))
        lab = jnp.asarray(rs.randint(0, 6, 8).astype(np.int32))
        f = jax.jit(lambda x: jnp.mean(pk.softmax_xent(x, lab)))
        want = float(jnp.mean(
            -jax.nn.log_softmax(x, -1)[jnp.arange(8), lab]))
        assert abs(float(f(x)) - want) <= 1e-5

    def test_large_row_tiling(self):
        """Rows beyond one tile (padding + multi-block grid path)."""
        rs = np.random.RandomState(4)
        b, c = 300, 2048  # forces row tiling with the 2^19 budget
        x = jnp.asarray(rs.randn(b, c).astype(np.float32))
        lab = jnp.asarray(rs.randint(0, c, b).astype(np.int32))
        got = pk.softmax_xent(x, lab)
        want = -jax.nn.log_softmax(x, -1)[jnp.arange(b), lab]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("rows", [8, 24, 64])
    @pytest.mark.parametrize("classes", [10, 1000, 50257])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bfloat16", "float32"])
    def test_logits_in_their_own_dtype_give_the_cast_first_result(
            self, dtype, classes, rows):
        """The kernels take the logits in the dtype they come in (the
        block's rows in tiles of it: 16 of bfloat16, 8 of float32; 24
        rows are padded, 8 are one short block) and upcast in VMEM:
        the loss is bit-equal to the one from logits cast to float32
        first, as the operator did before ISSUE 38, and dx is that
        path's dx rounded to the logits' dtype, written once. Padding
        rows (label -1, label == classes) keep zero loss and zero
        gradient; `jax.grad` through the `custom_vjp` is the direct
        call."""
        rs = np.random.RandomState(classes + rows)
        x = jnp.asarray(3 * rs.randn(rows, classes), jnp.float32
                        ).astype(dtype)
        lab = rs.randint(0, classes, rows).astype(np.int32)
        lab[1], lab[3] = -1, classes
        lab = jnp.asarray(lab)
        g = jnp.asarray(rs.rand(rows).astype(np.float32))

        cast = x.astype(jnp.float32)
        loss = pk.softmax_xent(x, lab)
        dx, _ = pk._softmax_xent_bwd((x, lab), g)
        want_dx, _ = pk._softmax_xent_bwd((cast, lab), g)
        assert loss.dtype == jnp.float32 and dx.dtype == dtype
        np.testing.assert_array_equal(loss, pk.softmax_xent(cast, lab))
        np.testing.assert_array_equal(
            np.asarray(dx, np.float32),
            np.asarray(want_dx.astype(dtype), np.float32))
        # the mathematics, not only the agreement of two calls
        ref = -jax.nn.log_softmax(cast, -1)[
            jnp.arange(rows), jnp.clip(lab, 0, classes - 1)]
        pad = np.array([1, 3])
        np.testing.assert_allclose(np.delete(loss, pad),
                                   np.delete(ref, pad), rtol=1e-5,
                                   atol=1e-5)
        assert not np.asarray(loss)[pad].any()
        assert not np.asarray(dx, np.float32)[pad].any()
        via_vjp = jax.grad(
            lambda a: jnp.sum(pk.softmax_xent(a, lab) * g))(x)
        np.testing.assert_array_equal(np.asarray(via_vjp, np.float32),
                                      np.asarray(dx, np.float32))

    def test_operator_keeps_bfloat16_logits_out_of_float32(self):
        """With the tier on, loss-and-gradient of `SoftMaxCrossEntropy`
        over bfloat16 logits holds no float32 array of the logits'
        shape: they go to the kernels, stay the residual and come back
        as dx in bfloat16 (the mechanism engaged, in test form). With
        the tier off the jnp branch casts first, as it always did."""
        rs = np.random.RandomState(9)
        x = jnp.asarray(rs.randn(32, 40), jnp.float32).astype(jnp.bfloat16)
        t = jnp.asarray(rs.randint(0, 40, 32).astype(np.int32))

        def loss_and_grad():
            # a function of its own a trace: jax keeps a function's
            # jaxpr, and the tier's switch is not among its arguments
            def f(x):
                op = autograd.SoftMaxCrossEntropy(t)
                loss = op.forward(x)
                return loss, op.backward(jnp.float32(1.0))
            return f

        def float32_logits(jaxpr):
            # everything that lives in HBM: the program's own values
            # and those of what it calls, but not a kernel's body
            found = []
            for eqn in jaxpr.eqns:
                found += [v.aval for v in eqn.outvars
                          if v.aval.shape == x.shape
                          and v.aval.dtype == jnp.float32]
                if eqn.primitive.name != "pallas_call":
                    for sub in jax.core.jaxprs_in_params(eqn.params):
                        found += float32_logits(sub)
            return found

        on = jax.make_jaxpr(loss_and_grad())(x)
        assert "pallas_call" in str(on)
        assert not float32_logits(on.jaxpr), on
        assert [v.aval.dtype for v in on.jaxpr.outvars] == [
            jnp.float32, jnp.bfloat16]
        l_on, g_on = loss_and_grad()(x)
        pk.enable(False)
        off = jax.make_jaxpr(loss_and_grad())(x)
        assert float32_logits(off.jaxpr)
        assert "pallas_call" not in str(off)
        l_off, g_off = loss_and_grad()(x)
        assert g_on.dtype == g_off.dtype == jnp.bfloat16
        assert abs(float(l_on) - float(l_off)) <= 1e-5
        np.testing.assert_allclose(np.asarray(g_on, np.float32),
                                   np.asarray(g_off, np.float32),
                                   rtol=1e-2, atol=1e-6)


class TestTopKSparsify:
    def test_threshold_keeps_at_least_k(self):
        rs = np.random.RandomState(5)
        flat = jnp.asarray(rs.randn(4096).astype(np.float32))
        for frac in (0.01, 0.05, 0.25):
            k = int(4096 * frac)
            y = pk.topk_sparsify(flat, frac)
            kept = int(jnp.sum(y != 0))
            assert kept >= k, (frac, kept, k)
            # conservative, but not wildly so (one histogram bin slack)
            assert kept <= k + 4096 // 128, (frac, kept, k)

    def test_mask_parity_with_jnp_at_same_threshold(self):
        rs = np.random.RandomState(6)
        flat = jnp.asarray(rs.randn(1000).astype(np.float32))
        thr = pk.topk_threshold(flat, 50)
        got = pk.threshold_mask(flat, thr)
        want = jnp.where(jnp.abs(flat) >= thr, flat, 0.0)
        np.testing.assert_array_equal(got, want)

    def test_kept_values_are_the_largest(self):
        rs = np.random.RandomState(7)
        flat = jnp.asarray(rs.randn(2048).astype(np.float32))
        y = np.asarray(pk.topk_sparsify(flat, 0.1))
        kept = np.abs(y[y != 0])
        dropped = np.abs(np.asarray(flat))[y == 0]
        assert kept.min() >= dropped.max() - 1e-6

    def test_communicator_sparsification_uses_kernel(self):
        from singa_tpu.dist.communicator import Communicator

        # the sparsifier is behind the opt-in ALL switch (routing
        # policy: parity-with-XLA kernels don't ship by default)
        pk.enable_all(True)
        try:
            assert pk.sparsify_enabled()
            comm = Communicator(world_size=1)
            rs = np.random.RandomState(8)
            g = jnp.asarray(rs.randn(32, 16).astype(np.float32))
            y = comm.sparsification(g, spars=0.1, topK=True)
            assert y.shape == g.shape
            kept = int(jnp.sum(y != 0))
            assert kept >= int(g.size * 0.1)
        finally:
            pk.enable_all(False)
            pk.enable(False)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="fused dropout uses the TPU on-core PRNG "
                           "(pltpu.prng_*): no interpreter emulation")
class TestDropoutTPU:
    def test_mask_ratio_and_scale(self):
        x = jnp.ones((256, 256), jnp.float32)
        y, m = pk.dropout(x, 0.3, 1234)
        keep = float(jnp.mean(m > 0))
        assert abs(keep - 0.7) < 0.05
        nz = np.asarray(y)[np.asarray(y) != 0]
        np.testing.assert_allclose(nz, 1.0 / 0.7, rtol=1e-5)


class TestEdgeCases:
    def test_padding_labels_match_jnp_path(self):
        """label=-1 (ignore/padding) must contribute zero loss, like
        jax.nn.one_hot's all-zero row in the stock path."""
        rs = np.random.RandomState(9)
        x = jnp.asarray(rs.randn(6, 4).astype(np.float32))
        lab = jnp.asarray([0, -1, 2, 3, -1, 1], np.int32)
        got = pk.softmax_xent(x, lab)
        onehot = jax.nn.one_hot(lab, 4)
        want = -jnp.sum(onehot * jax.nn.log_softmax(x, -1), -1)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        # grads must agree too (invalid rows get softmax*g)
        gp = jax.grad(lambda x: jnp.sum(pk.softmax_xent(x, lab)))(x)
        gr = jax.grad(lambda x: jnp.sum(
            -jnp.sum(onehot * jax.nn.log_softmax(x, -1), -1)))(x)
        np.testing.assert_allclose(gp, gr, rtol=1e-5, atol=1e-6)


class TestFlashAttention:
    """Fused flash-style attention vs the XLA plain_attention path."""

    def _qkv(self, b, h, s, d, seed=0):
        import jax.numpy as jnp

        rs = np.random.RandomState(seed)
        return tuple(jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))
                     for _ in range(3))

    @pytest.mark.parametrize("shape,causal", [
        ((2, 2, 64, 32), True),
        ((1, 3, 100, 16), False),   # non-multiple-of-tile seq (padding)
        ((2, 1, 192, 64), True),
    ])
    def test_fwd_and_grad_parity(self, shape, causal):
        import jax
        import jax.numpy as jnp

        from singa_tpu.parallel.ring_attention import plain_attention

        q, k, v = self._qkv(*shape)
        ref = plain_attention(q, k, v, causal=causal)
        got = pk.flash_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        f_ref = lambda q, k, v: jnp.sum(  # noqa: E731
            jnp.sin(plain_attention(q, k, v, causal=causal)))
        f_got = lambda q, k, v: jnp.sum(  # noqa: E731
            jnp.sin(pk.flash_attention(q, k, v, causal)))
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        gg = jax.grad(f_got, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gg):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-4, atol=1e-5)

    def test_attention_op_uses_kernel(self):
        from singa_tpu import autograd, tensor

        pk.enable(True)
        # drop the seq>=1024 crossover gate so the 64-token case still
        # exercises the autograd->kernel ROUTING (the gate itself is
        # perf policy, covered by test_attn_supported_crossover)
        saved_min = pk._ATTN_MIN_SEQ
        pk._ATTN_MIN_SEQ = 0
        try:
            q, k, v = self._qkv(1, 2, 64, 32)
            tq = tensor.from_raw(q, None)
            tk = tensor.from_raw(k, None)
            tv = tensor.from_raw(v, None)
            for t in (tq, tk, tv):
                t.requires_grad = True
            out = autograd.attention(tq, tk, tv, causal=True)
            from singa_tpu.parallel.ring_attention import plain_attention

            ref = plain_attention(q, k, v, causal=True)
            np.testing.assert_allclose(out.to_numpy(), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5)
        finally:
            pk._ATTN_MIN_SEQ = saved_min
            pk.enable(False)

    def test_vmem_budget_gate(self):
        assert pk.attn_supported(1024, 64)
        assert not pk.attn_supported(65536, 128)

    def test_cross_attention_falls_back(self):
        """Sq != Sk must NOT take the flash path (kernel assumes
        self-attention); the public op must still be correct."""
        import jax.numpy as jnp

        from singa_tpu import autograd, tensor
        from singa_tpu.parallel.ring_attention import plain_attention

        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(1, 2, 128, 32).astype(np.float32))
        k = jnp.asarray(rs.randn(1, 2, 64, 32).astype(np.float32))
        v = jnp.asarray(rs.randn(1, 2, 64, 32).astype(np.float32))
        pk.enable(True)
        try:
            tq, tk, tv = (tensor.from_raw(a, None) for a in (q, k, v))
            for t in (tq, tk, tv):
                t.requires_grad = True
            out = autograd.attention(tq, tk, tv, causal=False)
            ref = plain_attention(q, k, v, causal=False)
            np.testing.assert_allclose(out.to_numpy(), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5)
        finally:
            pk.enable(False)


def test_attn_supported_crossover_gate():
    """Routing policy: below the measured XLA crossover the fused
    kernel must NOT engage; above it (and within the VMEM budget) it
    must."""
    assert not pk.attn_supported(512, 64)      # 0.98x XLA: stay off
    assert pk.attn_supported(1024, 64)         # 1.14x: on
    assert pk.attn_supported(2048, 128)        # 1.27x: on
    assert not pk.attn_supported(1 << 16, 128)  # VMEM budget exceeded


def test_enable_all_implies_tier_on():
    saved_e, saved_a = pk._ENABLED, pk._ALL
    try:
        pk.enable(False)
        pk.enable_all(True)
        assert pk.enabled() and pk.dropout_enabled() \
            and pk.sparsify_enabled()
        pk.enable_all(False)
        assert pk.enabled() and not pk.dropout_enabled()
    finally:
        pk._ENABLED, pk._ALL = saved_e, saved_a
