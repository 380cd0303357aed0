"""Device/Platform tests. Reference model: `test_platform.cc` +
`python/singa/device.py` surface."""
import jax
import numpy as np
import pytest

from singa_tpu import device, tensor


def test_default_device_is_cpu():
    d = device.get_default_device()
    assert isinstance(d, device.CppCPU)
    assert d.lang == "cpp"
    # Singleton.
    assert device.get_default_device() is d


def test_create_accel_device():
    d = device.create_tpu_device()
    assert d.lang == "tpu"
    t = tensor.from_numpy(np.ones((2, 2), np.float32), device=d)
    np.testing.assert_array_equal(t.to_numpy(), np.ones((2, 2)))


def test_cpu_is_handed_out_only_when_asked_for(monkeypatch):
    """No fallback that hides the device: `create_tpu_device()` & co.
    return CPU devices only when the CPU is the platform jax was told
    to use first (conftest does that); when jax merely FELL BACK to
    the CPU — the TPU runtime failed to start — they raise, naming
    how to ask for the CPU on purpose."""
    assert device._cpu_requested()  # conftest: jax_platforms="cpu"
    assert len(device._accel_devices()) == 8  # the virtual devices
    before = jax.config.jax_platforms
    try:
        for asked, is_cpu in (("cpu", True), ("cpu,tpu", True),
                              ("tpu,cpu", False), ("tpu", False),
                              ("", False)):
            jax.config.update("jax_platforms", asked)
            assert device._cpu_requested() is is_cpu, asked
    finally:
        jax.config.update("jax_platforms", before)
    # jax fell back to the CPU unasked: nothing named a platform and
    # there is no TPU backend in this process
    monkeypatch.setattr(device, "_cpu_requested", lambda: False)
    for make in (device.create_tpu_device,
                 lambda: device.create_replica_device(1),
                 lambda: device.Platform.CreateTpuDevices(1),
                 lambda: device.create_tpu_device_on(0)):
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            make()


def test_importing_the_package_creates_no_backend():
    """A process that has created a jax backend holds the chip, so the
    parents that spawn chip children (`fleet_proc.ProcReplica`'s,
    `benchmarks/pallas_tune.py`) may import the package but nothing
    more."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-c",
         "import singa_tpu\n"
         "from singa_tpu import device, fleet_proc, serve, tuning\n"
         "print(device.backend_initialized())"],
        capture_output=True, text=True, timeout=120)
    assert proc.stdout.strip() == "False", proc.stdout + proc.stderr


def test_reference_alias_names():
    # Migration shims: reference spells these create_cuda_gpu*.
    assert device.create_cuda_gpu is device.create_tpu_device
    d = device.create_cuda_gpu()
    assert d.lang == "tpu"


def test_device_query_and_counts():
    q = device.Platform.DeviceQuery()
    assert "device(s)" in q
    assert device.Platform.GetNumCPUs() >= 1


def test_multiple_virtual_devices():
    # conftest forces 8 virtual CPU devices: the mesh substrate.
    devs = device.create_tpu_devices(8)
    assert len(devs) == 8
    ids = {d.id for d in devs}
    assert len(ids) == 8


def test_sync_noexcept():
    d = device.get_default_device()
    d.Sync()


def test_to_device_roundtrip():
    host = device.get_default_device()
    accel = device.create_tpu_device()
    a = np.random.RandomState(0).randn(4, 4).astype(np.float32)
    t = tensor.from_numpy(a, device=host)
    t.to_device(accel)
    assert t.device is accel
    t.to_host()
    np.testing.assert_array_equal(t.to_numpy(), a)


def test_profiling_table():
    d = device.get_default_device()
    d.ResetTimeProfiling()
    d.SetVerbosity(1)
    d.SetSkipIteration(0)
    with d.TimeOp("Add"):
        pass
    out = d.PrintTimeProfiling()
    assert "Add" in out
    d.SetVerbosity(0)


def test_graph_flag():
    d = device.get_default_device()
    assert not d.graph_enabled
    d.EnableGraph(True)
    assert d.graph_enabled
    d.EnableGraph(False)


def test_graph_mode_profiling_table():
    """VERDICT r1 #5: verbosity>0 + graph mode must yield a non-empty
    per-op table (measured step time + XLA cost breakdown)."""
    from singa_tpu import layer, model, opt

    class _M(model.Model):
        def __init__(self):
            super().__init__()
            self.fc1 = layer.Linear(16)
            self.relu = layer.ReLU()
            self.fc2 = layer.Linear(4)

        def forward(self, x):
            return self.fc2(self.relu(self.fc1(x)))

    d = device.create_tpu_device()
    d.ResetTimeProfiling()
    d.SetVerbosity(1)
    d.SetSkipIteration(0)
    try:
        m = _M()
        m.set_optimizer(opt.SGD(lr=0.1))
        x = tensor.from_numpy(
            np.random.RandomState(0).randn(8, 8).astype(np.float32),
            device=d)
        y = tensor.from_numpy(
            np.random.RandomState(1).randint(0, 4, 8).astype(np.int32),
            device=d)
        m.compile([x], is_train=True, use_graph=True)
        for _ in range(3):
            m(x, y)
        out = d.PrintTimeProfiling()
    finally:
        d.SetVerbosity(0)
        d.ResetTimeProfiling()
    assert "train_one_batch[graph]" in out
    assert "Graph (XLA) cost profile" in out
    assert "measured step" in out
    # the dot-bearing Linear layers must be attributed in the table
    assert "FLOPs" in out


def test_hlo_profile_parser_dot_flops():
    """The HLO cost parser computes exact dot FLOPs from contracting
    dims (2*M*N*K) on a jit-compiled matmul."""
    import jax
    import jax.numpy as jnp

    from singa_tpu import hlo_profile

    def f(a, b):
        return a @ b

    a = jnp.ones((8, 32), jnp.float32)
    b = jnp.ones((32, 16), jnp.float32)
    text = jax.jit(f).lower(a, b).compile().as_text()
    rows = hlo_profile.profile_hlo(text)
    dot_flops = sum(r["flops"] for r in rows if r["hlo"] in ("dot", "fusion"))
    assert dot_flops == 2 * 8 * 32 * 16, rows
