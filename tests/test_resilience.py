"""Fault-tolerance subsystem tests (`singa_tpu/resilience.py`, ISSUE 3).

Proves, on CPU, the guarantees production training leans on:

  - **StepGuard**: an injected-NaN step leaves params, optimizer
    slots, and the loss scale bit-identical to their pre-step values
    (except the scaler backoff), in eager AND graph mode, and the
    counters in `cache_stats()["resilience"]` increment.
  - **Mesh consistency**: the same model on a multi-virtual-device
    mesh makes the identical skip decision as the single-device run —
    the finite bit is computed over the global gradients inside the
    one SPMD program, so ranks cannot diverge.
  - **DynamicLossScaler**: power-of-two scales round-trip bit-exactly,
    grow after `growth_interval` clean steps, back off on overflow.
  - **Crash-consistent restore**: a truncated or bit-rotted newest
    checkpoint is skipped (content-digest manifest), not fatal, and a
    killed-mid-run training loop resumes to the exact loss trajectory
    of the uninterrupted run.
  - Satellites: async-writer errors carry the failed path; prefetch
    worker exceptions propagate to the consumer with the original
    traceback.

This file is the `-m 'not slow'`-safe fault-injection smoke required
by tier-1: everything here runs in seconds on the CPU backend.
"""
import numpy as np
import pytest

from singa_tpu import (
    autograd,
    checkpoint,
    data,
    device,
    layer,
    model,
    opt,
    resilience,
    stats,
    tensor,
)


class MLP(model.Model):
    def __init__(self, hidden=8, classes=3):
        super().__init__(name="mlp_resilience")
        self.fc1 = layer.Linear(hidden)
        self.relu = layer.ReLU()
        self.fc2 = layer.Linear(classes)

    def forward(self, x):
        return self.fc2(self.relu(self.fc1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = autograd.softmax_cross_entropy(out, y)
        self._optimizer.backward_and_update(loss)
        return out, loss


@pytest.fixture(autouse=True)
def _clean_resilience():
    """Guard/scaler config + state are process-global (like the cache
    knobs): reset around every test."""
    stats.reset_cache_stats()
    yield
    stats.configure(step_guard=False, loss_scaling=None)
    resilience.reset_state()


_X = np.random.RandomState(0).randn(16, 6).astype(np.float32)
_Y = np.random.RandomState(0).randint(0, 3, 16).astype(np.int32)


def _build(seed=7, use_graph=False, lr=0.1):
    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    tx = tensor.from_numpy(_X, device=dev)
    ty = tensor.from_numpy(_Y, device=dev)
    m = MLP()
    m.set_optimizer(opt.SGD(lr=lr, momentum=0.9))
    m.compile([tx], is_train=True, use_graph=use_graph)
    return m, tx, ty


def _params_np(m):
    return {k: np.asarray(v.to_numpy()) for k, v in m.get_states().items()}


def _slots_np(m):
    return {pid: {n: np.asarray(a) for n, a in st.items()}
            for pid, st in m._optimizer.states.items()}


def _nan_batch():
    xb = _X.copy()
    xb[0, 0] = np.nan
    return tensor.from_numpy(xb)


# ---------------------------------------------------------------------------
# StepGuard
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("use_graph", [False, True])
def test_nan_step_is_skipped_bit_identically(use_graph):
    device.set_step_guard(True)
    m, tx, ty = _build(use_graph=use_graph)
    for _ in range(2):  # materialize slots with clean steps
        m(tx, ty)
    before_p, before_s = _params_np(m), _slots_np(m)
    m(_nan_batch(), ty)  # poisoned input -> non-finite loss and grads
    after_p, after_s = _params_np(m), _slots_np(m)
    for k in before_p:
        np.testing.assert_array_equal(before_p[k], after_p[k])
    for pid in before_s:
        for n in before_s[pid]:
            np.testing.assert_array_equal(before_s[pid][n],
                                          after_s[pid][n])
    snap = stats.cache_stats()["resilience"]
    assert snap["steps_skipped"] == 1
    assert snap["steps_applied"] == 2
    # a clean step afterwards trains normally
    m(tx, ty)
    assert any((after_p[k] != v).any()
               for k, v in _params_np(m).items())
    assert stats.cache_stats()["resilience"]["steps_applied"] == 3


def test_unguarded_nan_step_corrupts_params():
    """Negative control: without the guard the NaN propagates into the
    parameters forever — the failure mode the guard exists for."""
    m, tx, ty = _build()
    m(tx, ty)
    m(_nan_batch(), ty)
    assert any(np.isnan(v).any() for v in _params_np(m).values())


def test_guard_counters_via_model_cache_stats():
    device.set_step_guard(True)
    m, tx, ty = _build()
    for _ in range(3):
        m(tx, ty)
    snap = m.cache_stats()["resilience"]
    assert snap["enabled"] is True
    assert snap["steps_applied"] == 3 and snap["steps_skipped"] == 0
    # the clean-step streak is a GUARD counter: it advances without
    # the scaler and resets on a skipped step
    assert snap["good_streak"] == 3
    m(_nan_batch(), ty)
    assert m.cache_stats()["resilience"]["good_streak"] == 0


def test_guard_stays_one_fused_executable():
    """The ≤1 % overhead mechanism, asserted structurally: the guarded
    eager step still runs as ONE cached fused executable — warmup
    traces only, zero retraces afterwards, one hit per step (what it
    costs on the chip: not measured)."""
    device.set_step_guard(True)
    m, tx, ty = _build()
    stats.reset_cache_stats()
    for _ in range(12):
        m(tx, ty)
    fused = stats.cache_stats()["fused_opt"]
    # step 1 creates slots (one trace), step 2 reaches steady state
    assert fused["misses"] <= 2
    assert fused["retraces"] == fused["misses"]
    assert fused["hits"] >= 10


# ---------------------------------------------------------------------------
# Mesh: every rank makes the identical skip decision
# ---------------------------------------------------------------------------
class _MeshMLP(model.Model):
    def __init__(self):
        super().__init__()
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


def test_mesh_skip_decision_matches_single_device():
    from singa_tpu.parallel import create_mesh

    device.set_step_guard(True)
    rs = np.random.RandomState(0)
    X = rs.randn(16, 32).astype(np.float32)
    Y = rs.randint(0, 10, (16,)).astype(np.int32)
    Xb = X.copy()
    Xb[0, 0] = np.nan

    def run(mesh):
        dev = device.get_default_device()
        dev.SetRandSeed(3)
        m = _MeshMLP()
        m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
        tx, ty = tensor.from_numpy(X), tensor.from_numpy(Y)
        m.compile([tx], is_train=True, use_graph=True, mesh=mesh)
        for _ in range(2):
            m(tx, ty)
        m(tensor.from_numpy(Xb), ty)  # the guarded step
        for _ in range(2):
            m(tx, ty)
        return _params_np(m)

    stats.reset_cache_stats()
    single = run(None)
    s1 = stats.cache_stats()["resilience"]
    resilience.reset_state()
    stats.reset_cache_stats()
    # 4x2 mesh: params sharded over "model", batch over "data" — the
    # finite bit reduces over the GLOBAL grads inside the SPMD program
    meshed = run(create_mesh({"data": 4, "model": 2}))
    s2 = stats.cache_stats()["resilience"]
    assert s1["steps_skipped"] == s2["steps_skipped"] == 1
    assert s1["steps_applied"] == s2["steps_applied"] == 4
    for k in single:
        np.testing.assert_allclose(single[k], meshed[k], atol=1e-5)


def test_distopt_driver_regime_whole_step_skip():
    """DistOpt's plain path makes the skip decision host-side on the
    already-reduced grads (identical on every rank by construction):
    a NaN step skips ALL param updates, counters advance once."""
    device.set_step_guard(True)
    dev = device.get_default_device()
    dev.SetRandSeed(7)
    tx = tensor.from_numpy(_X, device=dev)
    ty = tensor.from_numpy(_Y, device=dev)
    m = MLP()
    m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.1, momentum=0.9)))
    m.compile([tx], is_train=True, use_graph=False)
    for _ in range(2):
        m(tx, ty)
    before = _params_np(m)
    m(_nan_batch(), ty)
    for k, v in _params_np(m).items():
        np.testing.assert_array_equal(before[k], v)
    snap = stats.cache_stats()["resilience"]
    assert snap["steps_skipped"] == 1 and snap["steps_applied"] == 2
    assert snap["good_streak"] == 0  # streak resets on this path too
    m(tx, ty)
    assert stats.cache_stats()["resilience"]["good_streak"] == 1


# ---------------------------------------------------------------------------
# DynamicLossScaler
# ---------------------------------------------------------------------------
def test_loss_scaling_power_of_two_is_bit_exact():
    """scale→backward→unscale with a power-of-two scale is an exact
    exponent shift: the scaled run's params equal the unscaled run's
    bit for bit."""
    m0, tx, ty = _build(seed=5)
    for _ in range(4):
        m0(tx, ty)
    device.set_loss_scaling(init_scale=8.0, growth_interval=0)
    m1, tx, ty = _build(seed=5)
    for _ in range(4):
        m1(tx, ty)
    p0, p1 = _params_np(m0), _params_np(m1)
    for k in p0:
        np.testing.assert_array_equal(p0[k], p1[k])


@pytest.mark.parametrize("use_graph", [False, True])
def test_loss_scale_grows_and_backs_off(use_graph):
    device.set_loss_scaling(init_scale=8.0, growth_factor=2.0,
                            backoff_factor=0.5, growth_interval=2)
    m, tx, ty = _build(use_graph=use_graph)
    for _ in range(4):
        m(tx, ty)
    snap = stats.cache_stats()["resilience"]
    assert snap["loss_scale"] == 32.0  # grew at steps 2 and 4
    assert snap["scale_growths"] == 2
    before = _params_np(m)
    m(_nan_batch(), ty)  # overflow: skip + backoff, nothing else
    snap = stats.cache_stats()["resilience"]
    assert snap["loss_scale"] == 16.0
    assert snap["scale_backoffs"] == 1 and snap["steps_skipped"] == 1
    assert snap["good_streak"] == 0
    for k, v in _params_np(m).items():
        np.testing.assert_array_equal(before[k], v)


def test_loss_scaling_under_bf16_amp_trains():
    """The scaler's actual target: bf16 AMP. Scaled seed flows bf16
    through the backward, the fused update unscales, training
    descends, and the scale grows on schedule."""
    tensor.set_compute_dtype("bfloat16")
    try:
        device.set_loss_scaling(init_scale=256.0, growth_interval=3)
        m, tx, ty = _build(seed=11)
        losses = []
        for _ in range(9):
            _, loss = m(tx, ty)
            losses.append(float(loss.to_numpy()))
        assert losses[-1] < losses[0]
        snap = stats.cache_stats()["resilience"]
        assert snap["steps_applied"] == 9 and snap["steps_skipped"] == 0
        assert snap["loss_scale"] == 256.0 * 2 ** 3  # grew at 3, 6, 9
        for v in _params_np(m).values():
            assert np.isfinite(v).all()
    finally:
        tensor.set_compute_dtype(None)


def test_loss_scale_floors_at_min_scale():
    device.set_loss_scaling(init_scale=2.0, backoff_factor=0.5,
                            growth_interval=0, min_scale=1.0)
    m, tx, ty = _build()
    for _ in range(3):
        m(_nan_batch(), ty)
    assert stats.cache_stats()["resilience"]["loss_scale"] == 1.0


def test_loss_scale_growth_caps_at_max_scale():
    """All-zero/tiny grads keep the streak clean forever; uncapped
    growth would overflow the f32 scale to inf, from which backoff
    (inf * 0.5 == inf) could never recover."""
    device.set_loss_scaling(init_scale=4.0, growth_interval=1,
                            max_scale=16.0)
    m, tx, ty = _build(lr=0.0)  # lr 0: steps always clean
    for _ in range(5):
        m(tx, ty)
    snap = stats.cache_stats()["resilience"]
    assert snap["loss_scale"] == 16.0  # capped, not 4*2**5
    with pytest.raises(ValueError):
        device.set_loss_scaling(init_scale=2.0 ** 30, max_scale=2.0)


def test_distopt_skip_ignores_rank_local_loss():
    """The DistOpt host-side decision must key on the allreduced
    grads only: the loss is rank-local, and a rank skipping on its
    own overflowed loss while the reduced grads are finite would
    diverge the replicas."""
    dopt = opt.DistOpt(opt.SGD(lr=0.1))
    device.set_step_guard(True)
    p = tensor.from_numpy(np.ones(4, np.float32))
    g = tensor.from_numpy(np.ones(4, np.float32))
    inf_loss = tensor.from_numpy(np.asarray(np.inf, np.float32))
    assert dopt._guard_skip(inf_loss, [(p, g)]) is False  # applies
    bad_g = tensor.from_numpy(np.asarray([1, np.nan, 1, 1],
                                         np.float32))
    assert dopt._guard_skip(inf_loss, [(p, bad_g)]) is True  # skips


def test_reset_cache_stats_keeps_live_scale():
    """Observability reset must not change training behavior: the
    counters zero, the live loss scale (and growth streak) survive."""
    device.set_loss_scaling(init_scale=1024.0, growth_interval=0)
    m, tx, ty = _build()
    m(_nan_batch(), ty)  # back off: 1024 -> 512
    assert stats.cache_stats()["resilience"]["loss_scale"] == 512.0
    stats.reset_cache_stats()
    snap = stats.cache_stats()["resilience"]
    assert snap["loss_scale"] == 512.0  # NOT re-inited to 1024
    assert snap["steps_skipped"] == 0 and snap["scale_backoffs"] == 0


def test_distopt_does_not_drift_the_scaler():
    """DistOpt's driver path never scales the backward seed, so it
    must not grow/back off the scale either (a drifted scale would
    poison the scaled paths after a checkpoint round-trip)."""
    device.set_loss_scaling(init_scale=64.0, growth_interval=1)
    dev = device.get_default_device()
    dev.SetRandSeed(7)
    tx = tensor.from_numpy(_X, device=dev)
    ty = tensor.from_numpy(_Y, device=dev)
    m = MLP()
    m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.1, momentum=0.9)))
    m.compile([tx], is_train=True, use_graph=False)
    for _ in range(3):
        m(tx, ty)
    snap = stats.cache_stats()["resilience"]
    assert snap["loss_scale"] == 64.0  # frozen, not grown
    assert snap["steps_applied"] == 3


def test_restore_latest_all_corrupt_is_loud(tmp_path, capfd):
    d = str(tmp_path / "allbad")
    mgr = checkpoint.CheckpointManager(d, keep=3)
    m, _, _ = _build()
    resilience.run_resumable(m, mgr, _batch_fn, total_steps=3,
                             save_every=3)
    inj = resilience.FaultInjector(seed=0)
    inj.truncate_checkpoint(mgr._path(3))
    m2, _, _ = _build(seed=31)
    step, aux = mgr.restore_latest(m2)
    assert step is None and aux == {}
    assert dict(mgr.skipped_on_restore).keys() == {3}
    assert "NO valid checkpoint" in capfd.readouterr().err


def test_guard_state_checkpoint_roundtrip(tmp_path):
    """The scale/backoff history resumes with the weights."""
    device.set_loss_scaling(init_scale=8.0, growth_interval=2)
    m, tx, ty = _build()
    for _ in range(2):
        m(tx, ty)  # scale grows to 16
    m(_nan_batch(), ty)  # back off to 8, skipped=1
    path = str(tmp_path / "guard.zip")
    m.save_states(path)
    exported = resilience.export_host_state()
    resilience.reset_state()  # simulate a fresh process
    m2, _, _ = _build(seed=9)
    m2.load_states(path)
    assert resilience.export_host_state() == exported
    assert stats.cache_stats()["resilience"]["loss_scale"] == 8.0


# ---------------------------------------------------------------------------
# FaultInjector
# ---------------------------------------------------------------------------
def test_injector_is_deterministic_and_seed_keyed():
    a = resilience.FaultInjector(seed=7, schedule={"nan_grad": 0.3})
    b = resilience.FaultInjector(seed=7, schedule={"nan_grad": 0.3})
    c = resilience.FaultInjector(seed=8, schedule={"nan_grad": 0.3})
    da = [a.should("nan_grad", s) for s in range(64)]
    assert da == [b.should("nan_grad", s) for s in range(64)]
    assert da != [c.should("nan_grad", s) for s in range(64)]
    assert any(da) and not all(da)
    # unknown kind never fires
    assert not any(a.should("other", s) for s in range(64))
    # integer probabilities are probabilities, not step iterables
    always = resilience.FaultInjector(seed=1, schedule={"nan_grad": 1})
    assert all(always.should("nan_grad", s) for s in range(8))
    never = resilience.FaultInjector(seed=1, schedule={"nan_grad": 0})
    assert not any(never.should("nan_grad", s) for s in range(8))
    with pytest.raises(ValueError):
        resilience.FaultInjector(schedule={"nan_grad": 2.5})


def test_injector_explicit_schedule_and_actions():
    inj = resilience.FaultInjector(
        seed=1, schedule={"nan_batch": [3], "device_loss": [5],
                          "opt_state": [1]})
    m, tx, ty = _build()
    m(tx, ty)
    # nan_batch fires only at its step, leaves the original untouched
    assert inj.nan_batch(tx, step=2) is tx
    poisoned = inj.nan_batch(tx, step=3)
    assert np.isnan(np.asarray(poisoned.data)).any()
    assert not np.isnan(np.asarray(tx.data)).any()
    # optimizer-state corruption hits a slot
    assert inj.corrupt_optimizer_state(m._optimizer, step=1)
    assert any(np.isnan(np.asarray(a)).any()
               for st in m._optimizer.states.values()
               for a in st.values())
    inj.check_device_loss(step=4)  # not scheduled: no-op
    with pytest.raises(resilience.DeviceLostError):
        inj.check_device_loss(step=5)


def test_guard_catches_injected_optimizer_state_corruption():
    """NaN optimizer state poisons the NEXT update's slot math; with
    momentum, params go NaN without the guard. The guard's finite
    check covers loss+grads — state corruption converts to non-finite
    params only through the update, so this documents the repair
    recipe: corrupt slots are caught by restore, not the guard."""
    inj = resilience.FaultInjector(seed=1, schedule={"opt_state": [1]})
    m, tx, ty = _build()
    m(tx, ty)
    inj.corrupt_optimizer_state(m._optimizer, step=1)
    m(tx, ty)
    assert any(np.isnan(v).any() for v in _params_np(m).values())


# ---------------------------------------------------------------------------
# Crash-consistent checkpoints + auto-resume
# ---------------------------------------------------------------------------
def _batch_fn(step):
    rs = np.random.RandomState(1000 + step)
    x = rs.randn(16, 6).astype(np.float32)
    y = rs.randint(0, 3, 16).astype(np.int32)
    return tensor.from_numpy(x), tensor.from_numpy(y)


def test_manifest_written_and_corruption_fallback(tmp_path):
    """Satellite: truncate the newest checkpoint zip on disk —
    restore_latest recovers from the previous step and reports what
    it skipped; digest manifests also catch same-size bit-rot."""
    d = str(tmp_path / "ckpts")
    mgr = checkpoint.CheckpointManager(d, keep=3)
    m, _, _ = _build()
    resilience.run_resumable(m, mgr, _batch_fn, total_steps=12,
                             save_every=3)
    assert mgr.steps() == [6, 9, 12]
    import os

    for s in (6, 9, 12):
        assert os.path.exists(mgr._digest_path(s)), s
    inj = resilience.FaultInjector(seed=0)
    inj.truncate_checkpoint(mgr._path(12))  # kill-mid-write artifact
    inj.corrupt_checkpoint(mgr._path(9))    # silent same-size bit-rot
    m2, _, _ = _build(seed=21)
    step, aux = mgr.restore_latest(m2)
    assert step == 6
    assert aux.get("resumable_step") == 6
    skipped = dict(mgr.skipped_on_restore)
    assert set(skipped) == {12, 9}
    assert "size mismatch" in skipped[12]
    assert "digest mismatch" in skipped[9]


def test_kill_mid_run_resumes_to_identical_trajectory(tmp_path):
    """The headline resume guarantee: interrupt training mid-run,
    restart from the latest valid checkpoint, and the loss trajectory
    matches the uninterrupted run step for step."""
    # Uninterrupted reference run
    mgr_a = checkpoint.CheckpointManager(str(tmp_path / "a"), keep=3)
    m_a, _, _ = _build(seed=7)
    losses_a = m_a.fit_resumable(mgr_a, _batch_fn, total_steps=12,
                                 save_every=3)
    assert sorted(losses_a) == list(range(1, 13))

    # Interrupted run: device loss injected at step 8
    inj = resilience.FaultInjector(seed=3, schedule={"device_loss": [8]})

    def failing_batch_fn(step):
        inj.check_device_loss(step)
        return _batch_fn(step)

    mgr_b = checkpoint.CheckpointManager(str(tmp_path / "b"), keep=3)
    m_b, _, _ = _build(seed=7)
    with pytest.raises(resilience.DeviceLostError):
        m_b.fit_resumable(mgr_b, failing_batch_fn, total_steps=12,
                          save_every=3)
    mgr_b.wait_all()
    assert mgr_b.steps() == [3, 6]

    # Fresh process: different init seed proves state comes from the
    # checkpoint, not the model constructor
    m_b2, _, _ = _build(seed=99)
    mgr_b2 = checkpoint.CheckpointManager(str(tmp_path / "b"), keep=3)
    losses_b = m_b2.fit_resumable(mgr_b2, _batch_fn, total_steps=12,
                                  save_every=3)
    assert sorted(losses_b) == list(range(7, 13))  # resumed after 6
    for step, loss in losses_b.items():
        np.testing.assert_allclose(loss, losses_a[step], rtol=1e-6)


def test_resume_skips_corrupt_newest_checkpoint(tmp_path):
    """Kill mid-run AND corrupt the newest checkpoint: resume falls
    back one interval and still converges to the same trajectory."""
    mgr = checkpoint.CheckpointManager(str(tmp_path / "c"), keep=3)
    m, _, _ = _build(seed=7)
    losses_full = resilience.run_resumable(m, mgr, _batch_fn,
                                           total_steps=9, save_every=3)
    resilience.FaultInjector(seed=0).truncate_checkpoint(mgr._path(9))
    m2, _, _ = _build(seed=55)
    losses = resilience.run_resumable(m2, mgr, _batch_fn,
                                      total_steps=9, save_every=3)
    # restored from 6 (9 was corrupt), re-ran 7..9 identically
    assert sorted(losses) == [7, 8, 9]
    for step, loss in losses.items():
        np.testing.assert_allclose(loss, losses_full[step], rtol=1e-6)


# ---------------------------------------------------------------------------
# Satellites: writer-error path context, prefetch error propagation
# ---------------------------------------------------------------------------
def test_async_writer_error_names_the_failed_path(tmp_path):
    m, _, _ = _build()
    ckpt = checkpoint.AsyncCheckpointer()
    bad = str(tmp_path / "no_such_dir" / "x.zip")
    h = ckpt.save(m, bad)
    with pytest.raises(OSError) as ei:
        h.wait()
    blob = repr(ei.value.args) + "".join(
        getattr(ei.value, "__notes__", []))
    assert bad in blob


def test_wait_all_error_names_the_failed_path(tmp_path):
    m, _, _ = _build()
    ckpt = checkpoint.AsyncCheckpointer()
    bad = str(tmp_path / "nodir" / "y.zip")
    h = ckpt.save(m, bad)
    h._done.wait()  # caller discards the handle
    ckpt.save(m, str(tmp_path / "ok.zip"))
    with pytest.raises(OSError) as ei:
        ckpt.wait_all()
    blob = repr(ei.value.args) + "".join(
        getattr(ei.value, "__notes__", []))
    assert bad in blob


def test_failed_save_does_not_poison_restore(tmp_path, capfd,
                                             monkeypatch):
    """A transient write failure must surface ONCE and never block
    recovery: restore_latest reports it and restores from what is
    durably on disk; a second wait_all is clean."""
    d = str(tmp_path / "pois")
    mgr = checkpoint.CheckpointManager(d, keep=3)
    m, tx, ty = _build()
    m(tx, ty)
    mgr.save(m, step=1)
    mgr.wait_all()
    # inject a transient writer failure (ENOSPC-style)
    real_write = model.Model.write_states_zip

    def failing_write(fpath, states, meta):
        raise OSError("no space left on device (injected)")

    monkeypatch.setattr(model.Model, "write_states_zip",
                        staticmethod(failing_write))
    h = mgr.save(m, step=2)
    h._done.wait()
    assert h.error is not None
    monkeypatch.setattr(model.Model, "write_states_zip",
                        staticmethod(real_write))
    m2, _, _ = _build(seed=23)
    step, _aux = mgr.restore_latest(m2)  # must NOT raise
    assert step == 1
    assert "pending checkpoint write had failed" in \
        capfd.readouterr().err
    mgr.wait_all()  # error already surfaced: no stale re-raise


def test_failed_load_rolls_the_model_back(tmp_path):
    """A digest-valid but model-incompatible checkpoint must not leave
    a half-restored model behind: load_states mutates layer-by-layer,
    so restore_latest snapshots and rolls back before falling
    through."""

    class WiderMLP(model.Model):
        def __init__(self):
            super().__init__(name="mlp_resilience")  # same state names
            self.fc1 = layer.Linear(16)  # wider: shapes mismatch
            self.relu = layer.ReLU()
            self.fc2 = layer.Linear(3)

        def forward(self, x):
            return self.fc2(self.relu(self.fc1(x)))

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = autograd.softmax_cross_entropy(out, y)
            self._optimizer.backward_and_update(loss)
            return out, loss

    d = str(tmp_path / "mismatch")
    mgr = checkpoint.CheckpointManager(d, keep=3)
    m, tx, ty = _build()  # hidden=8
    m(tx, ty)
    mgr.save(m, step=1)
    mgr.wait_all()

    dev = device.get_default_device()
    dev.SetRandSeed(33)
    w = WiderMLP()
    w.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
    w.compile([tx], is_train=True, use_graph=False)
    w(tx, ty)
    pre = {k: np.asarray(v.to_numpy()) for k, v in w.get_states().items()}
    pre_step = w._optimizer.step_counter
    step, aux = mgr.restore_latest(w)
    assert step is None and aux == {}
    assert [s for s, _ in mgr.skipped_on_restore] == [1]
    assert "load failed" in mgr.skipped_on_restore[0][1]
    # the incompatible load left NO partial mutation behind
    for k, v in w.get_states().items():
        np.testing.assert_array_equal(pre[k], np.asarray(v.to_numpy()))
    assert w._optimizer.step_counter == pre_step
    w(tx, ty)  # still trainable from its clean state


def test_manifest_write_failure_does_not_fail_a_durable_save(
        tmp_path, capfd, monkeypatch):
    """The zip publish is the durability point; a digest-manifest
    failure after it leaves a valid (manifest-less legacy) checkpoint
    and must not surface as a failed save."""
    d = str(tmp_path / "manifail")
    mgr = checkpoint.CheckpointManager(d, keep=3)
    monkeypatch.setattr(
        checkpoint.CheckpointManager, "_file_digest",
        staticmethod(lambda p: (_ for _ in ()).throw(
            OSError("injected digest failure"))))
    m, tx, ty = _build()
    m(tx, ty)
    h = mgr.save(m, step=1)
    h.wait()  # must NOT raise: the zip is durable
    mgr.wait_all()
    assert "digest manifest write failed" in capfd.readouterr().err
    import os

    assert not os.path.exists(mgr._digest_path(1))
    monkeypatch.undo()
    m2, _, _ = _build(seed=29)
    step, _aux = mgr.restore_latest(m2)  # legacy-valid, loads fine
    assert step == 1


def test_distopt_finite_check_is_a_device_reduction():
    """The DistOpt skip decision reads ONE scalar from device, not the
    gradient bytes: host_all_finite reduces via all_finite on device."""
    import jax.numpy as jnp

    big = jnp.ones((1024, 256), jnp.float32)
    assert resilience.host_all_finite([big]) is True
    assert resilience.host_all_finite(
        [big, jnp.asarray(np.nan)]) is False
    # integer arrays are skipped, None tolerated
    assert resilience.host_all_finite(
        [None, jnp.ones(4, jnp.int32)]) is True


def test_snapshot_without_guard_touches_no_state():
    resilience.reset_state()
    snap = stats.cache_stats()["resilience"]
    assert snap == {"enabled": False, "loss_scaling": False,
                    "loss_scale": 1.0, "steps_applied": 0,
                    "steps_skipped": 0, "good_streak": 0,
                    "scale_growths": 0, "scale_backoffs": 0}
    assert resilience._STATE is None  # nothing materialized


def test_prefetch_worker_exception_propagates_with_traceback():
    """A mid-epoch pipeline failure reaches the consumer on the next
    __next__ — after the already-decoded batches — instead of ending
    the epoch silently, and carries the worker's traceback."""

    def source():
        yield np.ones(2), np.zeros(2)
        raise ValueError("decode failed on record 17")

    it = iter(data.BatchIter(source, prefetch=2))
    x, y = next(it)  # the batch before the failure is still delivered
    assert x.sum() == 2
    with pytest.raises(ValueError) as ei:
        next(it)
    blob = repr(ei.value.args) + "".join(
        getattr(ei.value, "__notes__", []))
    assert "decode failed on record 17" in blob
    assert "prefetch worker" in blob


def test_prefetch_epoch_without_failure_is_unaffected():
    def source():
        for i in range(5):
            yield np.full(2, i), np.zeros(2)

    items = list(data.BatchIter(source, prefetch=2))
    assert len(items) == 5
