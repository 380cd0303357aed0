"""Exhaustive per-op gradient audit (VERDICT r4 next #5).

Reference discipline: `test/python/test_operation.py` (~3,500 LoC,
SURVEY.md §4.2) checks EVERY autograd op's forward against numpy and
backward against numerical/analytic gradients. This file is the TPU
rebuild's equivalent, built as a registry sweep instead of 3.5k
hand-written lines:

  * `test_registry_fully_audited` enumerates every `Operator` subclass
    in `singa_tpu.autograd` and FAILS if any class is missing from the
    audit tables — adding an op without a gradient check breaks CI;
  * every differentiable op gets a central-difference check in
    float64 (`jax.enable_x64`) on the CPU backend: analytic grads from
    the op's own `backward` (vjp-derived or hand-written) vs
    (F(x+eps) - F(x-eps)) / 2eps of the cotangent-weighted output sum;
  * multi-output ops (Split, RNN) are checked against random
    cotangents on every output;
  * non-differentiable ops (comparisons, OneHot) are checked to
    refuse gradient flow;
  * stochastic / dtype ops (Dropout, Cast) get custom consistency
    checks (mask reuse in backward; dtype round-trip).

Large inputs are element-sampled (deterministic RandomState) to bound
runtime; every input of every op still gets >=1 sampled element.
"""
import inspect

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from singa_tpu import autograd, tensor
from singa_tpu.ops import native
from singa_tpu.ops.rnn import RNNHandle

MAX_ELEMS_PER_INPUT = 16  # sampled central-difference points per input


# ---------------------------------------------------------------------------
# machinery
# ---------------------------------------------------------------------------
def _run(make_op, arrays, requires_grad):
    """Fresh op on fresh tensors; returns (op, [output arrays])."""
    op = make_op()
    ts = []
    for a in arrays:
        # from_raw, not from_numpy: the public constructor downcasts
        # f64 -> f32 (reference convention), but the audit NEEDS f64
        # end-to-end for tight central-difference tolerances.
        t = tensor.from_raw(jnp.asarray(np.asarray(a)))
        t.requires_grad = requires_grad
        ts.append(t)
    outs = op(*ts)
    outs = outs if isinstance(outs, tuple) else (outs,)
    return op, [o.data for o in outs]


def _weighted_sum(make_op, arrays, cots):
    """Scalar F = sum_i <cot_i, y_i> — the function we differentiate."""
    _, ys = _run(make_op, arrays, requires_grad=False)
    return sum(float(jnp.vdot(c, y)) for c, y in zip(cots, ys))


def _grad_check(make_op, arrays, diff=None, eps=1e-5, rtol=1e-4,
                atol=1e-6, seed=0, train=False):
    """Analytic (op.backward) vs central-difference gradients in f64."""
    old_training = autograd.training
    autograd.training = train
    try:
        with jax.enable_x64():
            arrays = [np.asarray(a, np.float64)
                      if np.issubdtype(np.asarray(a).dtype, np.floating)
                      else np.asarray(a) for a in arrays]
            if diff is None:
                diff = [i for i, a in enumerate(arrays)
                        if np.issubdtype(a.dtype, np.floating)]
            rs = np.random.RandomState(seed)
            op, ys = _run(make_op, arrays, requires_grad=True)
            cots = [np.asarray(rs.randn(*y.shape), dtype=y.dtype)
                    for y in ys]
            grads = op.backward(*[jnp.asarray(c) for c in cots])
            if not isinstance(grads, (tuple, list)):
                grads = (grads,)
            assert len(grads) == len(arrays), (
                f"backward returned {len(grads)} grads for "
                f"{len(arrays)} inputs")
            for i in diff:
                ana = np.asarray(grads[i], np.float64).reshape(-1)
                flat = arrays[i].reshape(-1)
                n = flat.size
                idxs = (np.arange(n) if n <= MAX_ELEMS_PER_INPUT
                        else rs.choice(n, MAX_ELEMS_PER_INPUT,
                                       replace=False))
                for j in idxs:
                    orig = flat[j]
                    pert = [a.copy() for a in arrays]
                    pert[i].reshape(-1)[j] = orig + eps
                    fp = _weighted_sum(make_op, pert, cots)
                    pert[i].reshape(-1)[j] = orig - eps
                    fm = _weighted_sum(make_op, pert, cots)
                    num = (fp - fm) / (2.0 * eps)
                    np.testing.assert_allclose(
                        ana[j], num, rtol=rtol, atol=atol,
                        err_msg=f"input {i} element {j}")
    finally:
        autograd.training = old_training


_RS = np.random.RandomState(42)


def _rand(*shape):
    return _RS.randn(*shape)


def _pipe_audit_stage(p, h):
    """Homogeneous pipeline stage for the PipelineApply audit entry."""
    return jnp.tanh(h @ p["W"]) + h


# ---------------------------------------------------------------------------
# audit tables.  one entry per Operator subclass (enforced below).
# each: make_op, input arrays, optional kwargs for _grad_check.
# ---------------------------------------------------------------------------
A = autograd

# handles are shared across fresh op instances so jitted native calls
# (static_argnums on the handle) hit the jit cache per eval
_CONV = native.ConvHandle(2, 4, 3, stride=1, padding=1, bias=True)
_CONV_G = native.ConvHandle(4, 4, 3, stride=2, padding=1, groups=2,
                            bias=False)
_CONVT = native.ConvTransposeHandle(3, 2, 3, stride=2, padding=1,
                                    output_padding=1, bias=True)
_POOL_MAX = native.PoolingHandle(2, stride=2, padding=0, is_max=True)
_POOL_AVG = native.PoolingHandle(3, stride=2, padding=1, is_max=False,
                                 count_include_pad=False)
_BN = native.BatchNormHandle(factor=0.9, eps=1e-5)
_LSTM = RNNHandle(3, 4, 1, "lstm")
_GRU = RNNHandle(3, 4, 1, "gru")

# random op ATTRIBUTES are hoisted to constants: make_op runs once per
# function evaluation, so a fresh _rand() inside the lambda would make
# F a different function every call — garbage numerical gradients
_SCATTER_UPD = _rand(2, 3)
_MSE_T = _rand(3, 4)
_BCE_T = _RS.rand(3, 4).round().astype(np.float64)
_BCE_X = _RS.rand(3, 4) * 0.8 + 0.1
_sm = np.exp(_RS.randn(3, 5)); _SMCE_SOFT_T = _sm / _sm.sum(-1, keepdims=True)

DIFF_CASES = {
    # --- unary activations / elementwise ---------------------------------
    "ReLU": (A.ReLU, [_rand(3, 4)], {}),
    "Sigmoid": (A.Sigmoid, [_rand(3, 4)], {}),
    "Tanh": (A.Tanh, [_rand(3, 4)], {}),
    "Tanh_": (A.Tanh_, [_rand(3, 4)], {}),
    "SoftMax": (lambda: A.SoftMax(axis=1), [_rand(3, 5)], {}),
    "LogSoftMax": (lambda: A.LogSoftMax(axis=-1), [_rand(3, 5)], {}),
    "Abs": (A.Abs, [_rand(3, 4)], {}),
    "Exp": (A.Exp, [_rand(3, 4) * 0.5], {}),
    "Log": (A.Log, [np.abs(_rand(3, 4)) + 0.5], {}),
    "Sqrt": (A.Sqrt, [np.abs(_rand(3, 4)) + 0.5], {}),
    "Square": (A.Square, [_rand(3, 4)], {}),
    "Sign": (A.Sign, [_rand(3, 4)], {}),          # zero grad a.e.
    "Negative": (A.Negative, [_rand(3, 4)], {}),
    "Reciprocal": (A.Reciprocal, [np.abs(_rand(3, 4)) + 0.5], {}),
    "Erf": (A.Erf, [_rand(3, 4)], {}),
    "Ceil": (A.Ceil, [_rand(3, 4)], {}),          # zero grad a.e.
    "Floor": (A.Floor, [_rand(3, 4)], {}),
    "Round": (A.Round, [_rand(3, 4)], {}),
    "Clip": (lambda: A.Clip(-0.5, 0.5), [_rand(3, 4)], {}),
    "Cos": (A.Cos, [_rand(3, 4)], {}),
    "Sin": (A.Sin, [_rand(3, 4)], {}),
    "Tan": (A.Tan, [_rand(3, 4) * 0.5], {}),
    "Acos": (A.Acos, [_rand(3, 4) * 0.4], {}),
    "Asin": (A.Asin, [_rand(3, 4) * 0.4], {}),
    "Atan": (A.Atan, [_rand(3, 4)], {}),
    "Cosh": (A.Cosh, [_rand(3, 4)], {}),
    "Sinh": (A.Sinh, [_rand(3, 4)], {}),
    "Acosh": (A.Acosh, [np.abs(_rand(3, 4)) + 1.5], {}),
    "Asinh": (A.Asinh, [_rand(3, 4)], {}),
    "Atanh": (A.Atanh, [_rand(3, 4) * 0.4], {}),
    "Elu": (lambda: A.Elu(alpha=0.7), [_rand(3, 4)], {}),
    "SeLU": (A.SeLU, [_rand(3, 4)], {}),
    "LeakyRelu": (lambda: A.LeakyRelu(0.05), [_rand(3, 4)], {}),
    "HardSigmoid": (A.HardSigmoid, [_rand(3, 4)], {}),
    "SoftPlus": (A.SoftPlus, [_rand(3, 4)], {}),
    "SoftSign": (A.SoftSign, [_rand(3, 4)], {}),
    "Gelu": (A.Gelu, [_rand(3, 4)], {}),
    "Identity": (A.Identity, [_rand(3, 4)], {}),
    "Dummy": (lambda: A.Dummy(None), [_rand(3, 4)], {}),
    # --- binary ----------------------------------------------------------
    "Add": (A.Add, [_rand(3, 4), _rand(3, 4)], {}),
    "Sub": (A.Sub, [_rand(3, 4), _rand(3, 4)], {}),
    "Mul": (A.Mul, [_rand(3, 4), _rand(3, 4)], {}),
    "Div": (A.Div, [_rand(3, 4), np.abs(_rand(3, 4)) + 0.5], {}),
    "Pow": (A.Pow, [np.abs(_rand(3, 4)) + 0.5, _rand(3, 4)], {}),
    "Minimum": (A.Minimum, [_rand(3, 4), _rand(3, 4)], {}),
    "Maximum": (A.Maximum, [_rand(3, 4), _rand(3, 4)], {}),
    # --- matmul family ---------------------------------------------------
    "Mult": (A.Mult, [_rand(3, 4), _rand(4, 2)], {}),
    "Gemm": (lambda: A.Gemm(alpha=0.5, beta=1.5, transA=0, transB=1),
             [_rand(3, 4), _rand(2, 4), _rand(3, 2)], {}),
    "AddBias": (lambda: A.AddBias(axis=0), [_rand(3, 4), _rand(4)], {}),
    "Einsum": (lambda: A.Einsum("bij,bjk->bik"),
               [_rand(2, 3, 4), _rand(2, 4, 2)], {}),
    # --- shape ops -------------------------------------------------------
    "Reshape": (lambda: A.Reshape((2, 6)), [_rand(3, 4)], {}),
    "Flatten": (lambda: A.Flatten(axis=2), [_rand(2, 3, 4)], {}),
    "Transpose": (lambda: A.Transpose((1, 0, 2)), [_rand(2, 3, 4)], {}),
    "Concat": (lambda: A.Concat(axis=1),
               [_rand(2, 3), _rand(2, 2), _rand(2, 4)], {}),
    "Slice": (lambda: A.Slice([1], [5], axes=[1], steps=[2]),
              [_rand(3, 6)], {}),
    "SplitOp": (lambda: A.SplitOp(1, [2, 3]), [_rand(2, 5)], {}),
    "Gather": (lambda: A.Gather(1, np.array([0, 2, 4])),
               [_rand(3, 5)], {}),
    "Tile": (lambda: A.Tile((2, 3)), [_rand(2, 3)], {}),
    "Squeeze": (lambda: A.Squeeze(1), [_rand(3, 1, 4)], {}),
    "Unsqueeze": (lambda: A.Unsqueeze([0, 2]), [_rand(3, 4)], {}),
    "Pad": (lambda: A.Pad("constant", [0, 1, 2, 1], 0.5),
            [_rand(3, 4)], {}),
    "PadReflect": (lambda: A.Pad("reflect", [1, 1, 1, 1]),
                   [_rand(3, 4)], {}),
    "Expand": (lambda: A.Expand((3, 4)), [_rand(3, 1)], {}),
    "UpSample": (lambda: A.UpSample([1, 1, 2, 2]),
                 [_rand(1, 2, 3, 3)], {}),
    "DepthToSpace": (lambda: A.DepthToSpace(2, "DCR"),
                     [_rand(1, 8, 2, 2)], {}),
    "SpaceToDepth": (lambda: A.SpaceToDepth(2), [_rand(1, 2, 4, 4)], {}),
    "Where": (lambda: A.Where(np.array([[1, 0, 1, 0]] * 3)),
              [_rand(3, 4), _rand(3, 4)], {}),
    "ScatterElements": (
        lambda: A.ScatterElements(np.array([[0, 2, 1], [3, 0, 2]]),
                                  _SCATTER_UPD, axis=0),
        [_rand(4, 3)], {}),
    "Embedding": (lambda: A.Embedding(np.array([1, 3, 0, 3])),
                  [_rand(5, 4)], {}),
    # --- reductions ------------------------------------------------------
    "ReduceSum": (lambda: A.ReduceSum(axes=(1,), keepdims=True),
                  [_rand(3, 4, 2)], {}),
    "ReduceMean": (lambda: A.ReduceMean(axes=(0, 2), keepdims=False),
                   [_rand(3, 4, 2)], {}),
    "Max": (lambda: A.Max(axes=(1,)), [_rand(3, 5)], {}),
    "Min": (lambda: A.Min(axes=None), [_rand(3, 5)], {}),
    "GlobalAveragePool": (A.GlobalAveragePool, [_rand(2, 3, 4, 4)], {}),
    # --- losses (hand-written backwards — the audit's main targets) ------
    "SoftMaxCrossEntropy": (
        lambda: A.SoftMaxCrossEntropy(np.array([1, 0, 3])),
        [_rand(3, 5)],
        # forward pins fp32 (bf16-safe logsumexp); central diff noise
        # floor is f32 machine eps, so widen eps + tolerance
        {"eps": 1e-3, "rtol": 5e-3, "atol": 1e-3}),
    "SoftMaxCrossEntropySoft": (
        lambda: A.SoftMaxCrossEntropy(_SMCE_SOFT_T),
        [_rand(3, 5)],
        {"eps": 1e-3, "rtol": 5e-3, "atol": 1e-3}),
    "SoftMaxCrossEntropyPadded": (
        lambda: A.SoftMaxCrossEntropy(np.array([1, -1, 3])),
        [_rand(3, 5)],
        {"eps": 1e-3, "rtol": 5e-3, "atol": 1e-3}),
    "MeanSquareError": (
        lambda: A.MeanSquareError(_MSE_T), [_rand(3, 4)], {}),
    "BinaryCrossEntropy": (
        lambda: A.BinaryCrossEntropy(_BCE_T), [_BCE_X], {}),
    "LayerNorm": (lambda: A.LayerNorm(1e-5),
                  [_rand(2, 3, 4), _rand(4), _rand(4)], {}),
    "InstanceNorm": (lambda: A.InstanceNorm(1e-5),
                     [_rand(2, 3, 4, 4), _rand(3), _rand(3)],
                     {"rtol": 5e-4, "atol": 5e-6}),
    "Attention": (lambda: A.Attention(causal=True),
                  [_rand(1, 2, 4, 3), _rand(1, 2, 4, 3),
                   _rand(1, 2, 4, 3)], {}),
    "AttentionFull": (lambda: A.Attention(causal=False, scale=0.25),
                      [_rand(1, 1, 3, 4), _rand(1, 1, 3, 4),
                       _rand(1, 1, 3, 4)], {}),
    # --- NN ops over native handles --------------------------------------
    "_Conv2d": (lambda: A._Conv2d(_CONV),
                [_rand(2, 2, 5, 5), _rand(4, 2, 3, 3), _rand(4)], {}),
    "_Conv2dGrouped": (lambda: A._Conv2d(_CONV_G),
                       [_rand(1, 4, 5, 5), _rand(4, 2, 3, 3)], {}),
    "_ConvTranspose2d": (lambda: A._ConvTranspose2d(_CONVT),
                         [_rand(1, 3, 4, 4), _rand(3, 2, 3, 3),
                          _rand(2)], {}),
    "_Pooling2dMax": (lambda: A._Pooling2d(_POOL_MAX),
                      [_rand(1, 2, 4, 4)], {}),
    "_Pooling2dAvg": (lambda: A._Pooling2d(_POOL_AVG),
                      [_rand(1, 2, 5, 5)], {}),
    "_BatchNorm2dTrain": (
        lambda: A._BatchNorm2d(_BN, np.zeros(3), np.ones(3)),
        [_rand(2, 3, 4, 4), _rand(3), _rand(3)],
        {"train": True, "rtol": 5e-4, "atol": 5e-6}),
    "_BatchNorm2dEval": (
        lambda: A._BatchNorm2d(_BN, np.zeros(3), np.ones(3) * 2.0),
        [_rand(2, 3, 4, 4), _rand(3), _rand(3)], {"train": False}),
    "_RNN": (lambda: A._RNN(_LSTM),
             [_rand(3, 2, 3), _rand(1, 2, 4), _rand(1, 2, 4),
              _rand(_LSTM.weights_size)], {}),
    "_RNNGru": (lambda: A._RNN(_GRU),
                [_rand(3, 2, 3), _rand(1, 2, 4), _rand(1, 2, 4),
                 _rand(_GRU.weights_size)], {}),
    # --- multi-axis parallel ops (ISSUE 10; single-device paths:
    # PipelineApply runs its sequential composition, MoEFFN its dense
    # dispatch — the mesh variants are covered by tests/test_pipeline
    # and tests/test_moe parity suites) -----------------------------------
    "PipelineApply": (
        lambda: A.PipelineApply(_pipe_audit_stage, ("W",), 2),
        [_rand(3, 4), _rand(2, 4, 4) * 0.5], {}),
    # router math pins f32 (the GShard convention), so the central
    # difference floor is f32 eps — widen like SoftMaxCrossEntropy;
    # dropped_frac is stop_gradient'ed and piecewise constant, so its
    # cotangent contributes zero to both sides
    "MoEFFN": (
        lambda: A.MoEFFN(capacity_factor=1.5),
        [_rand(6, 4), _rand(4, 3) * 0.5, _rand(3, 4, 8) * 0.5,
         _rand(3, 8) * 0.1, _rand(3, 8, 4) * 0.5, _rand(3, 4) * 0.1],
        {"eps": 1e-3, "rtol": 5e-3, "atol": 1e-3}),
}

# non-differentiable ops: forward works, gradient flow is refused
NONDIFF_CASES = {
    "Less": (A.Less, [_rand(3, 4), _rand(3, 4)]),
    "Greater": (A.Greater, [_rand(3, 4), _rand(3, 4)]),
    "Equal": (A.Equal, [_rand(3, 4), _rand(3, 4)]),
    "OneHot": (lambda: A.OneHot(5), [np.array([1, 3, 0])]),
}

# ops with custom consistency checks below (stochastic / dtype)
CUSTOM_CASES = {"Dropout", "Cast"}


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------
def _registry():
    """Every Operator subclass defined in singa_tpu.autograd."""
    out = set()
    for name, obj in vars(autograd).items():
        if (inspect.isclass(obj) and issubclass(obj, autograd.Operator)
                and obj is not autograd.Operator):
            out.add(name)
    return out


def test_registry_fully_audited():
    """FAILS when an op class lacks an audit entry (VERDICT r4 #5:
    'any op without a grad check fails the sweep')."""
    audited = set()
    for key, (make_op, _arrays, _kw) in DIFF_CASES.items():
        op = make_op()
        audited.add(type(op).__name__)
    for key, (make_op, _arrays) in NONDIFF_CASES.items():
        audited.add(type(make_op()).__name__)
    audited |= CUSTOM_CASES
    missing = sorted(_registry() - audited)
    assert not missing, (
        f"autograd ops with NO gradient-audit entry: {missing} — add a "
        "case to tests/test_grad_audit.py")


@pytest.mark.parametrize("name", sorted(DIFF_CASES))
def test_gradient(name):
    make_op, arrays, kw = DIFF_CASES[name]
    _grad_check(make_op, arrays, **kw)


@pytest.mark.parametrize("name", sorted(NONDIFF_CASES))
def test_nondiff_refuses_grad(name):
    make_op, arrays = NONDIFF_CASES[name]
    op, ys = _run(make_op, arrays, requires_grad=True)
    assert not op.requires_grad, f"{name} must clear requires_grad"
    with pytest.raises(AssertionError):
        op.backward(jnp.ones_like(ys[0]))


def test_dropout_backward_reuses_forward_mask():
    """The backward must apply the SAME mask the forward sampled."""
    old = autograd.training
    autograd.training = True
    try:
        x = tensor.from_numpy(
            np.random.RandomState(0).randn(64, 32).astype(np.float32))
        x.requires_grad = True
        op = A.Dropout(ratio=0.5, rng_key=jax.random.PRNGKey(3))
        y = op(x)
        mask = np.asarray(y.data) / np.where(
            np.asarray(x.data) != 0, np.asarray(x.data), 1.0)
        dx = np.asarray(op.backward(jnp.ones_like(y.data)))
        np.testing.assert_allclose(dx, mask, rtol=1e-6)
        # kept elements are scaled by 1/keep, dropped are 0
        kept = mask[mask != 0]
        np.testing.assert_allclose(kept, 2.0, rtol=1e-6)
    finally:
        autograd.training = old


def test_dropout_eval_identity():
    old = autograd.training
    autograd.training = False
    try:
        x = tensor.from_numpy(np.ones((4, 4), np.float32))
        x.requires_grad = True
        op = A.Dropout(ratio=0.5)
        y = op(x)
        np.testing.assert_array_equal(np.asarray(y.data),
                                      np.asarray(x.data))
        dx = op.backward(jnp.full((4, 4), 3.0))
        np.testing.assert_allclose(np.asarray(dx), 3.0)
    finally:
        autograd.training = old


def test_cast_backward_restores_dtype():
    x = tensor.from_numpy(np.random.RandomState(0)
                          .randn(3, 4).astype(np.float32))
    x.requires_grad = True
    op = A.Cast(jnp.float16)
    y = op(x)
    assert y.data.dtype == jnp.float16
    dx = op.backward(jnp.ones((3, 4), jnp.float16))
    assert dx.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(dx), 1.0)
