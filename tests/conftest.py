"""Test configuration: force an 8-virtual-device CPU mesh.

Mirrors the reference's `test/python/cuda_helper.py` pattern (build
cpu/gpu device pairs, skip what's absent) but goes further: XLA's CPU
backend can simulate an 8-device TPU slice, so the collective /
sharding paths are CI-testable without hardware — something the
reference's NCCL backend could not do (SURVEY.md §4.3).

The CPU is asked for ON PURPOSE here (`jax_platforms="cpu"`), which is
what lets `device.create_tpu_device()` hand out CPU devices — the
framework never falls back to the host by itself. A plugin may have
imported jax and built a backend before this file runs, so after the
8-device flag is set any initialized backend is cleared and the CPU
client is (re)built with it.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from jax.extend.backend import clear_backends  # noqa: E402

clear_backends()
assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_dev():
    from singa_tpu import device

    return device.create_cpu_device()


@pytest.fixture(scope="session")
def default_dev():
    from singa_tpu import device

    return device.get_default_device()


def pytest_collection_modifyitems(config, items):
    """Deselect `slow`-marked tests by default (keeps the default run
    under the CI budget — VERDICT r4 next #8) WITHOUT the addopts
    trap: passing any -m expression (including -m "") or naming an
    explicit ::node id bypasses the filter, so
    `pytest tests/test_gan.py::test_vanilla_gan_moves_toward_ring`
    runs the test instead of silently collecting nothing."""
    args = [str(a) for a in config.invocation_params.args]
    if any(a == "-m" or a.startswith("-m=") or a.startswith("--markexpr")
           for a in args):
        return
    if any("::" in a for a in args):
        return
    selected = [i for i in items if "slow" not in i.keywords]
    deselected = [i for i in items if "slow" in i.keywords]
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = selected


def pytest_sessionstart(session):
    session.config._t1_t0 = __import__("time").time()
    session.config._t1_durations = {}


_DURATIONS = {}


def pytest_runtest_logreport(report):
    """Accumulate per-test wall clock (setup + call + teardown) so the
    session-end budget guard can NAME the heavy tests, not just warn
    that the tier is slow."""
    d = getattr(report, "duration", None)
    if d:
        _DURATIONS[report.nodeid] = _DURATIONS.get(report.nodeid,
                                                   0.0) + d


def pytest_sessionfinish(session, exitstatus):
    """Tier-1 wall-clock guard (ISSUE 17 satellite): the default
    (non-slow) run must stay inside the driver's pytest budget —
    creeping past it fails the WHOLE tier silently at the timeout
    kill, which reads as a hang, not a regression. Warn LOUDLY past
    90% of the budget so the session that added the weight sees it;
    non-fatal because a loaded CI box must not flake the tier.
    `SINGA_TPU_T1_BUDGET_S` overrides (0 disables)."""
    import time

    budget = float(os.environ.get("SINGA_TPU_T1_BUDGET_S", "870"))
    if budget <= 0 or not hasattr(session.config, "_t1_t0"):
        return
    took = time.time() - session.config._t1_t0
    # name the weight (ISSUE 20 satellite): the 10 slowest tests, so
    # the session that pushed the tier toward the budget sees WHICH
    # tests to shed to -m slow without a separate --durations run
    slowest = sorted(_DURATIONS.items(), key=lambda kv: -kv[1])[:10]
    if slowest:
        print(f"\n[t1-budget] {took:.0f}s of {budget:.0f}s budget; "
              "10 slowest tests:", flush=True)
        for nodeid, dur in slowest:
            print(f"  {dur:7.2f}s  {nodeid}", flush=True)
    if took > 0.9 * budget:
        import warnings

        warnings.warn(
            f"tier-1 wall clock {took:.0f}s is past 90% of the "
            f"{budget:.0f}s budget (SINGA_TPU_T1_BUDGET_S) — move the "
            "heaviest new tests behind -m slow before the driver's "
            "timeout kill turns this into a silent tier failure",
            stacklevel=0)
        print(f"\n[t1-budget] WARNING: {took:.0f}s of {budget:.0f}s "
              "budget used — shed weight to -m slow", flush=True)
