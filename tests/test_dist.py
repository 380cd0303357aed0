"""Distributed communicator tests on the 8-virtual-device CPU mesh.

The reference could only smoke-test DistOpt construction in CI (no
fake NCCL — SURVEY.md §4.3); here the collective path itself runs on
8 XLA CPU devices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from singa_tpu import autograd, opt, tensor
from singa_tpu.dist import Communicator, NcclIdHolder


@pytest.fixture(scope="module")
def comm():
    return Communicator(world_size=8)


def test_mesh_setup(comm):
    assert comm.world_size == 8
    assert comm.mesh.shape == {"dp": 8}


def test_synch_psum_under_shard_map(comm):
    # per-device distinct grads, synch must sum them (ncclAllReduce parity)
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    f = shard_map(
        lambda a: comm.synch(a),
        mesh=comm.mesh,
        in_specs=P("dp", None),
        out_specs=P("dp", None),
    )
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.full((8, 1), x.sum(), np.float32))


def test_fused_synch_under_shard_map(comm):
    a = np.ones((8, 4), np.float32)
    b = np.arange(16, dtype=np.float32).reshape(8, 2)

    def body(xa, xb):
        ra, rb = comm.fused_synch([xa, xb])
        return ra, rb

    f = shard_map(
        body, mesh=comm.mesh,
        in_specs=(P("dp", None), P("dp", None)),
        out_specs=(P("dp", None), P("dp", None)),
    )
    ra, rb = f(a, b)
    np.testing.assert_allclose(np.asarray(ra), np.full((8, 4), 8.0))
    np.testing.assert_allclose(
        np.asarray(rb), np.tile(b.reshape(8, 1, 2).sum(0), (8, 1))
    )


def test_synch_half_bf16_roundtrip(comm):
    x = np.full((8, 4), 0.5, np.float32)
    f = shard_map(
        lambda a: comm.synch_half(a), mesh=comm.mesh,
        in_specs=P("dp", None), out_specs=P("dp", None),
    )
    out = np.asarray(f(x))
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, np.full((8, 4), 4.0), rtol=1e-2)


def test_sparsification_threshold(comm):
    x = np.zeros((8, 4), np.float32)
    x[:, 0] = 1.0   # big entries survive
    x[:, 1] = 0.01  # below threshold: dropped
    f = shard_map(
        lambda a: comm.sparsification(a, spars=0.1), mesh=comm.mesh,
        in_specs=P("dp", None), out_specs=P("dp", None),
    )
    out = np.asarray(f(x))
    np.testing.assert_allclose(out[:, 0], np.full(8, 8.0))
    np.testing.assert_allclose(out[:, 1], np.zeros(8))


def test_sparsification_topk(comm):
    x = np.tile(np.array([[5.0, 0.1, 0.2, 3.0]], np.float32), (8, 1))
    f = shard_map(
        lambda a: comm.sparsification(a, spars=0.5, topK=True),
        mesh=comm.mesh, in_specs=P("dp", None), out_specs=P("dp", None),
    )
    out = np.asarray(f(x))
    np.testing.assert_allclose(out[0], [40.0, 0.0, 0.0, 24.0])


def test_driver_regime_identity(comm):
    # outside shard_map the value is already global: identity + scale 1
    x = jnp.ones((3,))
    out = comm.synch(x)
    comm.wait()
    np.testing.assert_allclose(np.asarray(out), np.ones(3))
    assert comm.grad_scale == 1.0


def test_shard_batch_layout(comm):
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    sx = comm.shard_batch(x)
    assert len(sx.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(sx), x)


def test_distopt_constructs_and_trains():
    # smoke: DistOpt drives a tiny model in driver regime
    rng = np.random.RandomState(0)
    x = tensor.from_numpy(rng.randn(16, 4).astype(np.float32))
    y = tensor.from_numpy(rng.randint(0, 2, 16).astype(np.int32))
    w = tensor.from_numpy(rng.randn(4, 2).astype(np.float32) * 0.1)
    w.requires_grad = True
    w.stores_grad = True

    sgd = opt.SGD(lr=0.1)
    dist = opt.DistOpt(sgd, nccl_id=NcclIdHolder(), local_rank=0)
    assert dist.world_size >= 1
    losses = []
    for _ in range(20):
        out = autograd.matmul(x, w)
        loss = autograd.softmax_cross_entropy(out, y)
        dist.backward_and_update(loss)
        losses.append(float(loss.to_numpy()))
    assert losses[-1] < losses[0]


def test_distopt_half_and_sparse_paths():
    rng = np.random.RandomState(1)
    x = tensor.from_numpy(rng.randn(16, 4).astype(np.float32))
    y = tensor.from_numpy(rng.randint(0, 2, 16).astype(np.int32))

    for method, kwargs in [
        ("backward_and_update_half", {}),
        ("backward_and_sparse_update", {"spars": 0.01, "topK": True}),
        ("backward_and_partial_update", {}),
    ]:
        w = tensor.from_numpy(rng.randn(4, 2).astype(np.float32) * 0.1)
        w.requires_grad = True
        w.stores_grad = True
        dist = opt.DistOpt(opt.SGD(lr=0.1))
        losses = []
        for _ in range(15):
            loss = autograd.softmax_cross_entropy(autograd.matmul(x, w), y)
            getattr(dist, method)(loss, **kwargs)
            losses.append(float(loss.to_numpy()))
        assert losses[-1] < losses[0], (method, losses)


def test_distopt_clip_norm_post_allreduce():
    """clip_norm on the wrapped optimizer scales the reduced grads:
    with lr=1 the single-param update delta has exactly norm clip."""
    rng = np.random.RandomState(3)
    x = tensor.from_numpy(rng.randn(16, 4).astype(np.float32))
    y = tensor.from_numpy(rng.randint(0, 2, 16).astype(np.int32))

    def one_step(clip):
        w = tensor.from_numpy(np.full((4, 2), 0.1, np.float32))
        w.requires_grad = True
        w.stores_grad = True
        sgd = opt.SGD(lr=1.0)
        sgd.clip_norm = clip
        dist = opt.DistOpt(sgd)
        before = w.to_numpy().copy()
        loss = autograd.softmax_cross_entropy(autograd.matmul(x, w), y)
        dist.backward_and_update(loss)
        return before - w.to_numpy()

    raw = one_step(None)
    gnorm = float(np.sqrt((raw ** 2).sum()))
    clipped = one_step(gnorm / 4)
    np.testing.assert_allclose(clipped, raw / 4, rtol=1e-5, atol=1e-7)

    # setting clip on the WRAPPER (public API) is honored too
    w = tensor.from_numpy(np.full((4, 2), 0.1, np.float32))
    w.requires_grad = True
    w.stores_grad = True
    dist = opt.DistOpt(opt.SGD(lr=1.0)).set_clip_norm(gnorm / 4)
    before = w.to_numpy().copy()
    loss = autograd.softmax_cross_entropy(autograd.matmul(x, w), y)
    dist.backward_and_update(loss)
    np.testing.assert_allclose(before - w.to_numpy(), raw / 4,
                               rtol=1e-5, atol=1e-7)
    # half path honors it too
    w = tensor.from_numpy(np.full((4, 2), 0.1, np.float32))
    w.requires_grad = True
    w.stores_grad = True
    sgd = opt.SGD(lr=1.0)
    sgd.clip_norm = gnorm / 4
    dist = opt.DistOpt(sgd)
    before = w.to_numpy().copy()
    loss = autograd.softmax_cross_entropy(autograd.matmul(x, w), y)
    dist.backward_and_update_half(loss)
    delta = before - w.to_numpy()
    np.testing.assert_allclose(np.sqrt((delta ** 2).sum()), gnorm / 4,
                               rtol=2e-2)  # bf16 round trip
