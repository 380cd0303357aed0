"""AOT export cache + shape bucketing (ISSUE 6).

Acceptance pins:
  - a warm start loads the serialized step executable WITHOUT tracing
    (export hits == 1, traces == 0) and produces BIT-identical loss to
    a freshly traced step — single device, process-fresh subprocess,
    and the 8-device CPU mesh;
  - a step-affecting knob change orphans the artifact (key miss);
  - a corrupt artifact falls back to tracing LOUDLY, never crashes;
  - the pow2 bucketing policy bounds retraces under randomized traffic
    to <= the number of buckets, errors loudly above the top bucket,
    and pad-to-bucket masking leaves loss bit-identical to the
    unpadded step on exact arithmetic;
  - `tools/export_cache_gc.py` lists / validates / collects the store.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from singa_tpu import device, export_cache, layer, model, opt, stats, \
    tensor
from singa_tpu.parallel import create_mesh

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _clean_export_config():
    """The export cache / bucket policy are process knobs: leaving
    them armed would reroute every later test through the AOT path."""
    yield
    export_cache.configure(directory=None, buckets=None)
    device.set_step_guard(False)


class TwoLayer(model.Model):
    def __init__(self):
        super().__init__()
        self.fc1 = layer.Linear(16)
        self.r1 = layer.ReLU()
        self.fc2 = layer.Linear(4)

    def forward(self, x):
        return self.fc2(self.r1(self.fc1(x)))


def _data(n=32, feats=8, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, feats).astype(np.float32)
    y = rs.randint(0, classes, n).astype(np.int32)
    return x, y


def _build(x, y, seed=0, mesh=None, use_graph=True):
    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    tx = tensor.from_numpy(x, device=dev)
    ty = tensor.from_numpy(y, device=dev)
    m = TwoLayer()
    m.set_optimizer(opt.SGD(lr=0.05, momentum=0.9))
    m.compile([tx], is_train=True, use_graph=use_graph, mesh=mesh)
    return m, tx, ty


def _export_snap():
    return stats.cache_stats()["export"]


# ---------------------------------------------------------------------------
# Warm start: hit, no tracing, bit-identical
# ---------------------------------------------------------------------------
def test_warm_start_is_hit_without_trace_and_bit_identical(tmp_path):
    device.set_export_cache(str(tmp_path))
    x, y = _data()
    m1, tx, ty = _build(x, y)
    s0 = _export_snap()
    losses_cold = [np.asarray(m1(tx, ty)[1].data).copy()
                   for _ in range(3)]
    s1 = _export_snap()
    assert s1["misses"] - s0["misses"] == 1
    assert s1["saves"] - s0["saves"] == 1
    assert s1["traces"] - s0["traces"] == 1
    # a fresh model (same topology/seed/knobs) warm-starts: the
    # artifact loads, nothing traces
    m2, tx2, ty2 = _build(x, y)
    losses_warm = [np.asarray(m2(tx2, ty2)[1].data).copy()
                   for _ in range(3)]
    s2 = _export_snap()
    assert s2["hits"] - s1["hits"] == 1
    assert s2["traces"] - s1["traces"] == 0
    assert s2["load_s"] > s1["load_s"]
    for lc, lw in zip(losses_cold, losses_warm):
        assert np.array_equal(lc, lw), "warm step drifted from traced"


def test_warm_start_process_fresh_subprocess(tmp_path):
    """The fleet contract: a PROCESS-FRESH worker finds the artifact,
    loads it without tracing (hits=1, traces=0, retraces=0), and its
    first-step loss is bit-identical to the tracing process's."""
    script = r"""
import sys, json
sys.path.insert(0, %(root)r)
import jax
jax.config.update("jax_platforms", "cpu")
from jax.extend.backend import clear_backends
clear_backends()
import numpy as np
from singa_tpu import device, layer, model, opt, stats, tensor

class TwoLayer(model.Model):
    def __init__(self):
        super().__init__()
        self.fc1 = layer.Linear(16)
        self.r1 = layer.ReLU()
        self.fc2 = layer.Linear(4)
    def forward(self, x):
        return self.fc2(self.r1(self.fc1(x)))

device.set_export_cache(%(cache)r)
dev = device.get_default_device()
dev.SetRandSeed(0)
rs = np.random.RandomState(0)
tx = tensor.from_numpy(rs.randn(32, 8).astype(np.float32), device=dev)
ty = tensor.from_numpy(rs.randint(0, 4, 32).astype(np.int32),
                       device=dev)
m = TwoLayer()
m.set_optimizer(opt.SGD(lr=0.05, momentum=0.9))
m.compile([tx], is_train=True, use_graph=True)
out, loss = m(tx, ty)
es = stats.cache_stats()["export"]
print(json.dumps({
    "loss_hex": np.asarray(loss.data).tobytes().hex(),
    "hits": es["hits"], "traces": es["traces"],
    "retraces": stats.cache_stats()["dag_backward"]["retraces"]}))
""" % {"root": _ROOT, "cache": str(tmp_path)}

    def run():
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    cold = run()
    warm = run()
    assert cold["hits"] == 0 and cold["traces"] == 1
    assert warm["hits"] == 1
    assert warm["traces"] == 0
    assert warm["retraces"] == 0
    assert warm["loss_hex"] == cold["loss_hex"]


def test_mesh_step_warm_start_bit_identical(tmp_path):
    """The sharded SPMD step serializes and warm-starts too, on the
    8-device CPU mesh, bit-identically."""
    device.set_export_cache(str(tmp_path))
    x, y = _data(n=32)
    m1, tx, ty = _build(x, y, mesh=create_mesh({"data": 8}))
    s0 = _export_snap()
    l1 = [np.asarray(m1(tx, ty)[1].data).copy() for _ in range(2)]
    s1 = _export_snap()
    assert s1["saves"] - s0["saves"] == 1
    m2, tx2, ty2 = _build(x, y, mesh=create_mesh({"data": 8}))
    l2 = [np.asarray(m2(tx2, ty2)[1].data).copy() for _ in range(2)]
    s2 = _export_snap()
    assert s2["hits"] - s1["hits"] == 1
    assert s2["traces"] - s1["traces"] == 0
    for a, b in zip(l1, l2):
        assert np.array_equal(a, b)


def test_knob_change_orphans_artifact(tmp_path):
    """A step-affecting knob flip (the step guard here) must change
    the key: loading yesterday's artifact under today's knobs would
    silently run the wrong program."""
    device.set_export_cache(str(tmp_path))
    x, y = _data()
    m1, tx, ty = _build(x, y)
    m1(tx, ty)
    s1 = _export_snap()
    device.set_step_guard(True)
    try:
        m2, tx2, ty2 = _build(x, y)
        m2(tx2, ty2)
    finally:
        device.set_step_guard(False)
    s2 = _export_snap()
    assert s2["hits"] - s1["hits"] == 0
    assert s2["misses"] - s1["misses"] == 1
    assert s2["saves"] - s1["saves"] == 1


def test_framework_source_edit_changes_the_key(tmp_path, monkeypatch):
    """The op lowerings and the optimizer update live in the package,
    not in the model class: a digest of the package's source files
    rides the key, so an artifact the OLD framework code exported can
    never load after an edit to autograd.py / opt.py / ops/."""
    pkg = tmp_path / "pkg"
    (pkg / "ops").mkdir(parents=True)
    (pkg / "autograd.py").write_text("x = 1\n")
    (pkg / "ops" / "native.py").write_text("y = 1\n")
    (pkg / "notes.txt").write_text("not source\n")
    d0 = export_cache.package_digest(str(pkg))
    (pkg / "notes.txt").write_text("still not source\n")
    export_cache.package_digest.cache_clear()
    assert export_cache.package_digest(str(pkg)) == d0
    (pkg / "ops" / "native.py").write_text("y = 2\n")
    export_cache.package_digest.cache_clear()
    d1 = export_cache.package_digest(str(pkg))
    assert d1 != d0
    export_cache.package_digest.cache_clear()

    x, y = _data()
    m, tx, ty = _build(x, y)
    args = (tx.data, ty.data)
    k0, parts = export_cache.step_key(m, m._optimizer, "step", args)
    assert parts["singa_tpu"] == export_cache.package_digest()
    monkeypatch.setattr(export_cache, "package_digest", lambda: d1)
    k1, _ = export_cache.step_key(m, m._optimizer, "step", args)
    assert k1 != k0


def test_per_model_grad_accum_override_keys_the_artifact(tmp_path):
    """`Model.compile(grad_accum=n)` bakes a DIFFERENT program than
    the monolithic step even when the process knob says 1 — the two
    must never share an artifact (the scan-fused accum-4 step loading
    into an unaccumulated model would be silent wrong math)."""
    device.set_export_cache(str(tmp_path))
    x, y = _data(n=32)
    dev = device.get_default_device()
    dev.SetRandSeed(0)
    tx = tensor.from_numpy(x, device=dev)
    ty = tensor.from_numpy(y, device=dev)
    m = TwoLayer()
    m.set_optimizer(opt.SGD(lr=0.05, momentum=0.9))
    m.compile([tx], is_train=True, use_graph=True, grad_accum=4)
    m(tx, ty)
    s1 = _export_snap()
    assert s1["saves"] >= 1
    m2, tx2, ty2 = _build(x, y)  # same shapes, accum OFF
    m2(tx2, ty2)
    s2 = _export_snap()
    assert s2["hits"] - s1["hits"] == 0, (
        "accum-4 artifact must not load into an unaccumulated step")
    assert s2["misses"] - s1["misses"] == 1


def test_resumed_step_counter_still_warm_starts(tmp_path):
    """The optimizer step counter is a TRACED program input, not
    program structure: a run resumed at step 1000 must hit the
    artifact saved at step 0 (keying on the value would make every
    resume a miss and grow the store per starting step)."""
    device.set_export_cache(str(tmp_path))
    x, y = _data()
    m1, tx, ty = _build(x, y)
    m1(tx, ty)
    s1 = _export_snap()
    m2, tx2, ty2 = _build(x, y)
    m2._optimizer.step_counter = 1000  # checkpoint-resumed process
    m2(tx2, ty2)
    s2 = _export_snap()
    assert s2["hits"] - s1["hits"] == 1
    assert s2["traces"] - s1["traces"] == 0


def test_training_mode_forward_is_never_bucket_padded():
    """Bucketing pads only EVAL forwards: a training-mode forward
    writes BN-style state back from the program, and stats over a
    padded batch would be silently reweighted."""
    x, y = _data(n=16)
    m, tx, ty = _build(x, y)
    m.train(True)
    device.set_shape_buckets(max_batch=32)
    s0 = _export_snap()["bucket_pads"]
    out = m.forward_graph(tensor.from_numpy(x[:5]))
    assert out.shape[0] == 5
    assert _export_snap()["bucket_pads"] == s0


def test_layer_config_attrs_key_the_fingerprint(tmp_path):
    """Two instances with IDENTICAL param shapes but a different
    scalar config attribute (a causal flag, a stride...) trace
    different programs — they must never share an artifact."""

    class Scaled(model.Model):
        def __init__(self, k):
            super().__init__()
            self.k = k
            self.fc = layer.Linear(4)

        def forward(self, x):
            return self.fc(x) * self.k

    device.set_export_cache(str(tmp_path))
    x, y = _data()

    def build(k):
        dev = device.get_default_device()
        dev.SetRandSeed(0)
        tx = tensor.from_numpy(x, device=dev)
        m = Scaled(k)
        m.compile([tx], is_train=False, use_graph=True)
        m.eval()
        return m, tx

    m1, tx = build(1.0)
    m2, _ = build(2.0)
    assert m1.topology_fingerprint() != m2.topology_fingerprint()
    s0 = _export_snap()
    m1(tx)
    s1 = _export_snap()
    assert s1["saves"] - s0["saves"] == 1
    m2(tx)  # same shapes, different config: MUST miss
    s2 = _export_snap()
    assert s2["hits"] - s1["hits"] == 0
    assert s2["misses"] - s1["misses"] == 1


def test_knob_fingerprint_tracks_pallas_tier():
    from singa_tpu.ops import pallas_kernels as pk

    base = export_cache.knob_fingerprint()
    assert base["pallas"] == pk.enabled()
    saved = pk.enabled()
    try:
        pk.enable(not saved)
        assert export_cache.knob_fingerprint()["pallas"] == (not saved)
    finally:
        pk.enable(saved)


def test_lr_and_schedule_hyperparams_key_the_artifact(tmp_path):
    """The optimizer's learning rate is baked into the traced program
    as a constant — an artifact saved at lr=0.1 loading into an
    lr=0.001 run would silently train at the wrong rate. Plain floats
    and schedule OBJECTS (callable instances whose hyperparams live in
    __dict__) must both key."""
    device.set_export_cache(str(tmp_path))
    x, y = _data()

    def build(lr):
        dev = device.get_default_device()
        dev.SetRandSeed(0)
        tx = tensor.from_numpy(x, device=dev)
        ty = tensor.from_numpy(y, device=dev)
        m = TwoLayer()
        m.set_optimizer(opt.SGD(lr=lr, momentum=0.9))
        m.compile([tx], is_train=True, use_graph=True)
        return m, tx, ty

    m1, tx, ty = build(0.1)
    m1(tx, ty)
    s1 = _export_snap()
    m2, tx2, ty2 = build(0.001)
    m2(tx2, ty2)
    s2 = _export_snap()
    assert s2["hits"] - s1["hits"] == 0, (
        "lr change must orphan the artifact")
    assert s2["misses"] - s1["misses"] == 1
    # schedule objects: same class, different decay constant
    sched = export_cache._scalarize(opt.ExponentialDecay(
        0.1, 100, 0.9)) if hasattr(opt, "ExponentialDecay") else None
    if sched is not None:
        sched2 = export_cache._scalarize(opt.ExponentialDecay(
            0.1, 100, 0.5))
        assert sched != sched2, (
            "schedule hyperparams collapsed out of the fingerprint")


def test_disarming_store_mid_run_recovers_polymorphic_step(tmp_path):
    """configure(directory=None) after warm steps must not strand the
    shape-specialized Exported executable: the next new shape rebuilds
    the plain polymorphic jit instead of erroring."""
    device.set_export_cache(str(tmp_path))
    x, y = _data(n=32)
    m, tx, ty = _build(x, y)
    loss_a = np.asarray(m(tx, ty)[1].data).copy()
    export_cache.configure(directory=None)
    x16, y16 = _data(n=16, seed=1)
    out = m(tensor.from_numpy(x16), tensor.from_numpy(y16))
    assert out[0].shape[0] == 16  # new shape retraced, no error
    assert np.isfinite(loss_a).all()


def test_corrupt_artifact_falls_back_loudly(tmp_path, capfd):
    device.set_export_cache(str(tmp_path))
    x, y = _data()
    m1, tx, ty = _build(x, y)
    loss_cold = np.asarray(m1(tx, ty)[1].data).copy()
    arts = [f for f in os.listdir(tmp_path) if f.endswith(".jexp")]
    assert len(arts) == 1
    with open(os.path.join(tmp_path, arts[0]), "r+b") as f:
        f.truncate(max(1, os.path.getsize(
            os.path.join(tmp_path, arts[0])) // 2))
    s1 = _export_snap()
    m2, tx2, ty2 = _build(x, y)
    loss_again = np.asarray(m2(tx2, ty2)[1].data).copy()
    s2 = _export_snap()
    err = capfd.readouterr().err
    assert "failed to load" in err and "falling back to tracing" in err
    assert s2["errors"] - s1["errors"] >= 1
    assert s2["hits"] - s1["hits"] == 0
    assert s2["traces"] - s1["traces"] == 1  # re-traced, re-published
    assert np.array_equal(loss_cold, loss_again)


def test_sonnx_model_warm_starts_and_keys_on_graph(tmp_path):
    """ONNX-imported models warm-start too, and two DIFFERENT graphs
    with this class never share a fingerprint (the graph digest, not
    the Python source, is the identity)."""
    sys.path.insert(0, os.path.join(_ROOT, "examples", "onnx"))
    from bert import build_bert_onnx

    from singa_tpu import sonnx

    device.set_export_cache(str(tmp_path))

    def build(layers):
        dev = device.get_default_device()
        dev.SetRandSeed(0)
        mp = build_bert_onnx(97, 16, 32, 4, layers, 4, seed=3)
        m = sonnx.SONNXModel(mp)
        m.set_optimizer(opt.SGD(lr=0.01))
        rs = np.random.RandomState(0)
        tx = tensor.from_numpy(
            rs.randint(0, 97, (2, 16)).astype(np.int32), device=dev)
        ty = tensor.from_numpy(rs.randint(0, 4, 2).astype(np.int32),
                               device=dev)
        m.compile([tx], is_train=True, use_graph=True)
        return m, tx, ty

    m1, tx, ty = build(layers=1)
    m2, _, _ = build(layers=2)
    assert m1.topology_fingerprint() != m2.topology_fingerprint()
    s0 = _export_snap()
    loss_cold = np.asarray(m1(tx, ty)[1].data).copy()
    s1 = _export_snap()
    assert s1["saves"] - s0["saves"] == 1
    m3, tx3, ty3 = build(layers=1)
    loss_warm = np.asarray(m3(tx3, ty3)[1].data).copy()
    s2 = _export_snap()
    assert s2["hits"] - s1["hits"] == 1
    assert s2["traces"] - s1["traces"] == 0
    assert np.array_equal(loss_cold, loss_warm)


# ---------------------------------------------------------------------------
# Retrace-storm diagnosis (satellite)
# ---------------------------------------------------------------------------
def test_step_retrace_warns_with_old_and_new_shapes(capfd):
    x, y = _data(n=32)
    m, tx, ty = _build(x, y)
    m(tx, ty)
    s0 = _export_snap()["step_retraces"]
    x2, y2 = _data(n=16, seed=1)
    m(tensor.from_numpy(x2), tensor.from_numpy(y2))
    err = capfd.readouterr().err
    assert "step retrace after warmup" in err
    assert "float32[32,8]" in err and "float32[16,8]" in err
    assert _export_snap()["step_retraces"] - s0 == 1
    # the SAME pair again is not a new storm: warn once per new shape
    m(tx, ty)
    m(tensor.from_numpy(x2), tensor.from_numpy(y2))
    assert _export_snap()["step_retraces"] - s0 == 1


def test_warm_load_of_new_shape_is_not_a_retrace(tmp_path, capfd):
    """A warm process serving two shapes from a populated store must
    NOT alarm: deserializing the second shape's artifact is a load,
    not a retrace — the provisioning counter stays flat."""
    device.set_export_cache(str(tmp_path))
    x32, y32 = _data(n=32)
    x16, y16 = _data(n=16, seed=1)
    m1, tx, ty = _build(x32, y32)
    m1(tx, ty)
    m1(tensor.from_numpy(x16), tensor.from_numpy(y16))  # populates
    capfd.readouterr()
    s0 = _export_snap()["step_retraces"]
    m2, tx2, ty2 = _build(x32, y32)
    m2(tx2, ty2)
    m2(tensor.from_numpy(x16), tensor.from_numpy(y16))  # warm load
    assert _export_snap()["step_retraces"] == s0
    assert "step retrace" not in capfd.readouterr().err


# ---------------------------------------------------------------------------
# Shape bucketing
# ---------------------------------------------------------------------------
def test_bucket_policy_edges():
    pol = export_cache.BucketPolicy(max_batch=64)
    assert pol.bucket_batch(1) == 1
    assert pol.bucket_batch(3) == 4
    assert pol.bucket_batch(64) == 64  # exactly on a boundary: no pad
    assert pol.bucket_batch(33) == 64
    with pytest.raises(export_cache.BucketOverflowError,
                       match="exceeds the largest"):
        pol.bucket_batch(65)
    with pytest.raises(ValueError, match="power of two"):
        export_cache.BucketPolicy(max_batch=48)
    # half-configured seq bucketing is a loud error, not dead code
    with pytest.raises(ValueError, match="max_seq missing"):
        export_cache.BucketPolicy(max_batch=8, seq_dim=1)
    with pytest.raises(ValueError, match="seq_dim missing"):
        export_cache.BucketPolicy(max_batch=8, max_seq=16)
    assert export_cache.BucketPolicy(max_batch=64).n_buckets() == 7
    seq = export_cache.BucketPolicy(max_batch=8, seq_dim=1, max_seq=16)
    assert seq.bucket_seq(9) == 16
    assert seq.n_buckets() == 4 * 5


def test_bucketed_forward_bounds_retraces_under_random_traffic():
    """30 random batch sizes in [1, 64] must trace at most
    log2(64)+1 = 7 distinct programs — the provisioning bound — and
    every reply must come back at its REAL size."""
    x, y = _data(n=64)
    m, tx, ty = _build(x, y)
    m.eval()
    device.set_shape_buckets(max_batch=64)
    # `buckets_seen` is one set a process: start this test's count
    # clean, whatever ran in the worker before it
    stats.reset_cache_stats()
    rs = np.random.RandomState(7)
    sizes = [int(s) for s in rs.randint(1, 65, size=30)]
    for n in sizes:
        out = m(tensor.from_numpy(x[:n]))
        assert out.shape[0] == n
    fwd = m._jit_fwd
    assert len(fwd._compiled) == 1  # one jit, shapes retrace inside
    jitted = next(iter(fwd._compiled.values()))
    n_buckets = export_cache.BucketPolicy(max_batch=64).n_buckets()
    assert jitted._cache_size() <= n_buckets
    snap = _export_snap()
    assert 0 < snap["buckets_seen"] <= n_buckets
    assert snap["bucket_pads"] > 0


def test_bucketed_forward_bounds_retraces_batch_and_seq_traffic():
    """Batch AND sequence dims randomized together: traces stay
    bounded by the 2D bucket grid, replies keep their real sizes."""

    class Pointwise(model.Model):
        def forward(self, x):
            from singa_tpu import autograd

            return autograd.relu(x)

    dev = device.get_default_device()
    m = Pointwise()
    tx = tensor.from_numpy(np.zeros((4, 8), np.float32), device=dev)
    m.compile([tx], is_train=False, use_graph=True)
    m.eval()
    device.set_shape_buckets(max_batch=16, seq_dim=1, max_seq=32)
    rs = np.random.RandomState(3)
    for _ in range(25):
        n, s = int(rs.randint(1, 17)), int(rs.randint(1, 33))
        out = m(tensor.from_numpy(rs.randn(n, s).astype(np.float32)))
        assert out.shape == (n, s)
    jitted = next(iter(m._jit_fwd._compiled.values()))
    pol = export_cache.BucketPolicy(max_batch=16, seq_dim=1,
                                    max_seq=32)
    assert jitted._cache_size() <= pol.n_buckets()


def test_bucketed_forward_overflow_is_loud():
    x, y = _data(n=64)
    m, tx, ty = _build(x, y)
    m.eval()
    device.set_shape_buckets(max_batch=32)
    with pytest.raises(export_cache.BucketOverflowError):
        m(tensor.from_numpy(x[:33]))


def test_bucketed_forward_matches_unbucketed_bit_exact():
    """Pad rows are sliced back off: the bucketed reply for n=13 must
    be bit-identical to the policy-off reply (row-independent ops)."""
    x, y = _data(n=16)
    m, tx, ty = _build(x, y)
    m.eval()
    ref = np.asarray(m(tensor.from_numpy(x[:13])).data).copy()
    device.set_shape_buckets(max_batch=32)
    got = np.asarray(m(tensor.from_numpy(x[:13])).data).copy()
    assert got.shape == ref.shape
    assert np.array_equal(ref, got)


def test_pad_to_bucket_masked_loss_bit_identical():
    """On exact (dyadic) arithmetic, the masked-sum loss over a padded
    bucket equals the unpadded mean loss BIT-for-bit: pad rows
    contribute exact zeros, and sum/n is the same division."""
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    # dyadic inputs: every product/sum below is exact in fp32
    x = (rs.randint(-8, 8, (6, 4)) / 4.0).astype(np.float32)
    w = (rs.randint(-8, 8, (4, 1)) / 8.0).astype(np.float32)
    ytrue = (rs.randint(-8, 8, (6, 1)) / 2.0).astype(np.float32)
    n, target = 6, 8

    def per_sample(xa, ya):
        d = xa @ w - ya
        return (d * d).sum(axis=1)

    unpadded = per_sample(jnp.asarray(x), jnp.asarray(ytrue))
    loss_ref = jnp.sum(unpadded) / n
    (xp, yp), n_real = export_cache.pad_batch([x, ytrue], target), n
    mask = export_cache.batch_mask(n_real, target)
    padded = per_sample(jnp.asarray(xp), jnp.asarray(yp))
    loss_masked = jnp.sum(padded * jnp.asarray(mask)) / jnp.sum(
        jnp.asarray(mask))
    assert np.asarray(loss_masked).tobytes() == \
        np.asarray(loss_ref).tobytes()


def test_pad_batch_to_bucket_repeats_final_sample():
    x = np.arange(12, dtype=np.float32).reshape(6, 2)
    pol = export_cache.BucketPolicy(max_batch=16)
    (xp,), info = export_cache.pad_batch_to_bucket([x], pol)
    assert (info["n_real"], info["n_bucket"]) == (6, 8)
    assert xp.shape == (8, 2)
    assert np.array_equal(np.asarray(xp[6]), x[-1])
    assert np.array_equal(np.asarray(xp[7]), x[-1])
    # exactly on a bucket edge: untouched
    (xp2,), info2 = export_cache.pad_batch_to_bucket([x[:4]], pol)
    assert (info2["n_real"], info2["n_bucket"]) == (4, 4)
    assert xp2.shape == (4, 2)
    # seq bucketing pads dim 1 by repeating the final position and
    # reports the slicing recipe
    spol = export_cache.BucketPolicy(max_batch=8, seq_dim=1,
                                     max_seq=8)
    (xs,), sinfo = export_cache.pad_batch_to_bucket(
        [np.arange(10, dtype=np.float32).reshape(2, 5)], spol)
    assert xs.shape == (2, 8)
    assert (sinfo["seq_real"], sinfo["seq_bucket"]) == (5, 8)
    assert np.array_equal(np.asarray(xs[:, 5:]),
                          np.repeat(np.asarray(xs[:, 4:5]), 3, axis=1))


def test_bucketing_bounds_export_artifacts(tmp_path):
    """Store + policy together: diverse traffic fills at most one
    artifact per bucket — the disk-side half of the provisioning
    bound."""
    device.set_export_cache(str(tmp_path))
    device.set_shape_buckets(max_batch=32)
    x, y = _data(n=32)
    m, tx, ty = _build(x, y)
    m.eval()
    for n in (3, 5, 9, 17, 31, 32, 2, 7):
        m(tensor.from_numpy(x[:n]))
    arts = [f for f in os.listdir(tmp_path) if f.endswith(".jexp")]
    n_buckets = export_cache.BucketPolicy(max_batch=32).n_buckets()
    assert 0 < len(arts) <= n_buckets


# ---------------------------------------------------------------------------
# GC tool
# ---------------------------------------------------------------------------
def _load_gc():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "export_cache_gc_for_test",
        os.path.join(_ROOT, "tools", "export_cache_gc.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gc_tool_lists_validates_and_collects(tmp_path, capsys):
    device.set_export_cache(str(tmp_path))
    x, y = _data()
    m1, tx, ty = _build(x, y)
    m1(tx, ty)
    m1.eval()
    m1(tx)  # second artifact (forward)
    arts = sorted(f for f in os.listdir(tmp_path)
                  if f.endswith(".jexp"))
    assert len(arts) == 2
    gc = _load_gc()
    assert gc.main(["--dir", str(tmp_path), "list"]) == 0
    out = capsys.readouterr().out
    assert "2 artifact(s)" in out and "OK" in out
    assert gc.main(["--dir", str(tmp_path), "validate"]) == 0
    capsys.readouterr()
    # corrupt one artifact: validate goes red, gc collects it
    victim = os.path.join(tmp_path, arts[0])
    with open(victim, "r+b") as f:
        f.write(b"\x00garbage")
    assert gc.main(["--dir", str(tmp_path), "validate"]) == 1
    out = capsys.readouterr().out
    assert "INVALID" in out and "digest mismatch" in out
    assert gc.main(["--dir", str(tmp_path), "gc", "--dry-run"]) == 0
    assert os.path.exists(victim), "--dry-run must not delete"
    capsys.readouterr()
    assert gc.main(["--dir", str(tmp_path), "gc"]) == 0
    assert not os.path.exists(victim)
    assert not os.path.exists(victim + ".json"), "manifest collected"
    survivors = [f for f in os.listdir(tmp_path)
                 if f.endswith(".jexp")]
    assert survivors == [arts[1]]


def test_pad_batch_to_bucket_skips_scalar_leader():
    """A 0-d first input (a scalar timestep, say) must not crash or
    be mistaken for the batch: the first >=1-d array leads."""
    pol = export_cache.BucketPolicy(max_batch=16)
    t = np.float32(0.5)  # 0-d
    x = np.zeros((6, 2), np.float32)
    (t2, xp), info = export_cache.pad_batch_to_bucket([t, x], pol)
    assert (info["n_real"], info["n_bucket"]) == (6, 8)
    assert xp.shape == (8, 2) and np.asarray(t2).ndim == 0
    # no batched array at all: untouched, nothing to slice
    (t3,), info2 = export_cache.pad_batch_to_bucket([t], pol)
    assert info2["n_real"] is None and info2["n_bucket"] is None


def test_sonnx_fingerprint_keys_subclass_scalar_config():
    """A fine-tune subclass's constructor-set scalar (baked into the
    traced program) must key the ONNX fingerprint like any layer
    config attr."""
    sys.path.insert(0, os.path.join(_ROOT, "examples", "onnx"))
    from bert import build_bert_onnx

    from singa_tpu import sonnx

    mp = build_bert_onnx(97, 16, 32, 4, 1, 4, seed=3)

    class FT(sonnx.SONNXModel):
        def __init__(self, onnx_model, temperature):
            super().__init__(onnx_model)
            self.temperature = temperature

    assert FT(mp, 1.0).topology_fingerprint() != \
        FT(mp, 4.0).topology_fingerprint()


def test_gc_tool_age_cutoff_and_orphan_manifests(tmp_path, capsys):
    device.set_export_cache(str(tmp_path))
    x, y = _data()
    m1, tx, ty = _build(x, y)
    m1(tx, ty)
    art = [f for f in os.listdir(tmp_path) if f.endswith(".jexp")][0]
    man = os.path.join(tmp_path, art + ".json")
    # age the artifact ten days via its manifest timestamp
    with open(man) as f:
        data = json.load(f)
    data["created"] -= 10 * 86400
    with open(man, "w") as f:
        json.dump(data, f)
    # plus an orphan manifest (artifact deleted externally) and a
    # stale tmp file (writer killed mid-save, aged past the grace
    # window)
    with open(os.path.join(tmp_path, "deadbeef.jexp.json"), "w") as f:
        json.dump({"sha256": "", "size": 0}, f)
    tmp_file = os.path.join(tmp_path, "cafe.jexp.tmp.1234")
    with open(tmp_file, "wb") as f:
        f.write(b"partial")
    old = os.path.getmtime(tmp_file) - 2 * 3600
    os.utime(tmp_file, (old, old))
    gc = _load_gc()
    assert gc.main(["--dir", str(tmp_path), "gc",
                    "--older-than-days", "7"]) == 0
    out = capsys.readouterr().out
    assert "older than" in out and "orphan manifest" in out
    assert "stale tmp" in out
    assert not any(f.endswith(".jexp") for f in os.listdir(tmp_path))
    assert not os.path.exists(
        os.path.join(tmp_path, "deadbeef.jexp.json"))
    assert not os.path.exists(tmp_file)
