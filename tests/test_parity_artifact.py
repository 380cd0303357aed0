"""CIFAR-10 loss-parity gate (BASELINE.md row 2; VERDICT r1 #7).

Reference: graph-vs-eager loss equality is the reference's key model
test invariant (test/python/test_model.py, SURVEY.md §4.2); the
committed PARITY_cifar10.json extends it across backends (host CPU
vs TPU chip)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_cifar_cnn_eager_vs_graph_parity_small():
    """Regenerates the core parity property at small scale in-process:
    same CNN config, eager vs jit curves within tolerance."""
    sys.path.insert(0, _ROOT)
    from tools.parity_cifar10 import max_rel_diff, train_curve

    eager = train_curve("cpu", False, steps=4)
    graph = train_curve("cpu", True, steps=4)
    assert len(eager) == len(graph) == 4
    assert max_rel_diff(eager, graph) <= 2e-2, (eager, graph)
    # and training actually trains
    assert graph[-1] < graph[0]


def test_committed_artifact_is_valid():
    """The committed PARITY_cifar10.json must exist, carry the CPU
    pair within its recorded tolerance, and keep the TPU slot
    (curve or an explicit error record)."""
    path = os.path.join(_ROOT, "PARITY_cifar10.json")
    assert os.path.exists(path), "run tools/parity_cifar10.py"
    with open(path) as f:
        art = json.load(f)
    tol = art["config"]["tolerance_rel"]
    diffs = art["max_rel_diffs"]
    assert "cpu_eager_vs_cpu_graph" in diffs
    assert diffs["cpu_eager_vs_cpu_graph"] <= tol
    assert all(v <= tol for v in diffs.values()), diffs
    assert "tpu_graph" in art["curves"]
    if art["curves"]["tpu_graph"] is None:
        assert art["errors"].get("tpu_graph"), \
            "missing TPU curve must be explained"


def test_committed_artifact_descends_below_plateau():
    """VERDICT r5 next #4: the compared trajectory must be a real
    descent — the CPU curve ends >=0.5 below the ln(10) plateau, and
    the pairwise max_rel is reported (and within tolerance) at the
    steepest-descent region, where divergence would actually show."""
    path = os.path.join(_ROOT, "PARITY_cifar10.json")
    with open(path) as f:
        art = json.load(f)
    d = art.get("descent")
    assert d, "artifact missing descent metrics"
    assert d["descended"] is True
    assert d["min_loss"] <= d["plateau"] - 0.5
    tol = art["config"]["tolerance_rel"]
    at_descent = art.get("max_rel_at_descent", {})
    assert "cpu_eager_vs_cpu_graph" in at_descent
    assert all(v <= tol for v in at_descent.values()), at_descent


def test_failed_tpu_attempt_never_erases_recorded_column(tmp_path):
    """A parity run whose TPU curve fails (the chip run died midway)
    must keep the recorded on-chip artifact intact — the acceptance
    gate's evidence must be monotone."""
    import shutil
    import subprocess
    import sys

    art = os.path.join(_ROOT, "PARITY_cifar10.json")
    with open(art) as f:
        before = f.read()
    if not json.loads(before).get("curves", {}).get("tpu_graph"):
        pytest.skip("no recorded tpu_graph column to protect")
    backup = tmp_path / "parity_backup.json"
    shutil.copy(art, backup)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools",
                                          "parity_cifar10.py"),
             "--tpu-only", "--skip-tpu", "--steps", "30"],
            capture_output=True, text=True, timeout=120, cwd=_ROOT)
        assert proc.returncode == 0, proc.stderr[-1000:]
        with open(art) as f:
            after = f.read()
        assert after == before, (
            "tool rewrote the artifact, nulling the recorded column")
    finally:
        shutil.copy(backup, art)
