"""Cost-model-guided autotuner (`singa_tpu.tuning` +
`tools/autotune.py`; ISSUE 9).

The contract: a DETERMINISTIC search over the step knob space, scored
without a chip by the HLO meters + a roofline cost model —

  * same seed, same proposals, same winner (no wall clock, no global
    RNG in the search),
  * the winner's measured `bytes_accessed` is STRICTLY lower than the
    default's, and a remat config's `peak_bytes_estimate` is strictly
    lower too (THE acceptance property: the search finds real byte
    wins on CPU),
  * unchanged configs hit the score cache (HLO-neutral knobs share a
    measurement),
  * unknown knob names/values are refused loudly,
  * the best-known config round-trips the persisted store (by
    fingerprint and by alias; corrupt stores read empty, never crash),
  * measured scores (Pallas sweep JSONL, config-tagged metrics JSONL)
    outrank the model on exact matches,
  * the CLI smoke (tiny model, <=8 candidates, CPU-only) runs in
    tier-1.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from singa_tpu import (autograd, device, layer, model, opt, stats,
                       tensor, tuning)

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class TuneNet(model.Model):
    def __init__(self):
        super().__init__(name="autotune_net")
        self.conv1 = layer.Conv2d(8, 3, padding=1)
        self.bn1 = layer.BatchNorm2d()
        self.relu = layer.ReLU()
        self.flat = layer.Flatten()
        self.fc = layer.Linear(5)

    def forward(self, x):
        h = self.relu(self.bn1(self.conv1(x)))
        return self.fc(self.flat(h))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = autograd.softmax_cross_entropy(out, y)
        self._optimizer.backward_and_update(loss)
        return out, loss


@pytest.fixture(autouse=True)
def _reset_knobs():
    yield
    device.set_remat_policy(None)
    device.set_grad_accum(1)
    device.set_bn_stats_dtype(None)
    tensor.set_compute_dtype(None)
    device.set_parallel_plan(None)
    stats.configure(pipeline_microbatches=None,
                    moe_capacity_factor=None)


def _factory():
    dev = device.get_default_device()
    dev.SetRandSeed(11)
    return TuneNet(), opt.SGD(lr=0.1, momentum=0.9)


def _inputs(bs=8):
    rs = np.random.RandomState(0)
    x = tensor.from_numpy(rs.randn(bs, 3, 8, 8).astype(np.float32))
    y = tensor.from_numpy(rs.randint(0, 5, bs).astype(np.int32))
    return [x, y]


def _scorer(**kw):
    return tuning.CostModelScorer(_factory, _inputs, chip="v5e", **kw)


# A reduced space for fast in-process searches: every knob present
# (the scorer's HLO key wants them all), values a subset of KNOBS.
# The multi-axis knobs (ISSUE 10) are pinned to their defaults here —
# the dedicated multi-axis tests below open them up.
SMALL_SPACE = dict(
    tuning.KNOBS,
    compute_dtype=(None,),
    slot_dtype=(None, "bfloat16"),
    bn_stats_dtype=(None,),
    xla_profile=("default", "latency"),
    grad_accum=(1, 2),
    remat_policy=(None, "dots_saveable"),
    mesh_geometry=(None,),
    pipeline_microbatches=(None,),
    moe_capacity_factor=(None,),
    pallas_attn_tq=(None,),
    pallas_row_budget=(None,),
    pallas_hist_budget=(None,),
)


# ---------------------------------------------------------------------------
# config validation: refusal of unknown knobs
# ---------------------------------------------------------------------------
def test_unknown_knob_name_refused():
    with pytest.raises(ValueError, match="unknown knob name"):
        tuning.validate_config({"slot_dtypo": "bfloat16"})


def test_unknown_knob_value_refused():
    with pytest.raises(ValueError, match="unknown value"):
        tuning.validate_config({"slot_dtype": "fp8"})


def test_missing_knobs_fill_with_defaults():
    cfg = tuning.validate_config({"slot_dtype": "bfloat16"})
    assert cfg["slot_dtype"] == "bfloat16"
    assert cfg["grad_accum"] == 1 and cfg["remat_policy"] is None
    assert tuning.default_config() == tuning.validate_config({})


def test_store_put_refuses_unknown_knobs(tmp_path):
    store = tuning.TunedStore(str(tmp_path / "s.json"))
    with pytest.raises(ValueError, match="unknown knob"):
        store.put("fp", "v5e", {"bogus": 1}, 1.0)


# ---------------------------------------------------------------------------
# deterministic proposals + search
# ---------------------------------------------------------------------------
def test_propose_deterministic_and_seeded():
    a = tuning.propose(budget=40, seed=1)
    b = tuning.propose(budget=40, seed=1)
    assert a == b
    c = tuning.propose(budget=40, seed=2)
    assert c != a  # the random fill is seed-keyed
    # the first candidate is always the default baseline, and the
    # single-flip sweep precedes the random fill
    assert a[0] == tuning.default_config()
    canon = {tuning.canonical(x) for x in a}
    assert len(canon) == len(a), "duplicate proposals"


def test_greedy_combo_diffs_against_snapped_baseline():
    """With a Pallas sweep armed, every candidate (the baseline
    included) carries the snapped measured-best blocks; the greedy
    combination must diff flips against THAT baseline, or no row
    would ever differ by exactly one knob and the exploitation slot
    would silently never fire."""
    space = {"a": (0, 1), "b": (0, 1), "p": (None, 7)}
    base = {"a": 0, "b": 0, "p": 7}  # p snapped to the measured best
    rows = [
        {"config": base, "score": 1.0, "feasible": True, "i": 0},
        {"config": dict(base, a=1), "score": 2.0, "feasible": True,
         "i": 1},
        {"config": dict(base, b=1), "score": 3.0, "feasible": True,
         "i": 2},
    ]
    combo = tuning._greedy_combo(rows, space)
    assert combo == {"a": 1, "b": 1, "p": 7}


def test_search_stable_winner_on_repeat():
    r1 = tuning.autotune(_scorer(), budget=6, seed=3,
                         space=SMALL_SPACE)
    r2 = tuning.autotune(_scorer(), budget=6, seed=3,
                         space=SMALL_SPACE)
    assert r1["best"] == r2["best"]
    assert r1["best_score"] == r2["best_score"]
    assert ([r["config"] for r in r1["rows"]]
            == [r["config"] for r in r2["rows"]])


# ---------------------------------------------------------------------------
# THE acceptance property: the winner's measured bytes are strictly
# lower than the default's (and a remat config's peak is, too)
# ---------------------------------------------------------------------------
def test_winner_beats_default_with_strictly_lower_bytes():
    res = tuning.autotune(_scorer(), budget=8, seed=0,
                          space=SMALL_SPACE)
    assert res["beats_default"], res
    assert res["best_row"]["bytes"] < res["default_row"]["bytes"], (
        res["best_row"]["bytes"], res["default_row"]["bytes"])


class DeepNet(model.Model):
    """Two conv blocks at 16x16: enough activation depth that the
    dots_saveable saveable set is smaller than the full residual walk
    (a single tiny conv isn't — region inputs dominate its peak)."""

    def __init__(self):
        super().__init__(name="autotune_deep")
        self.conv1 = layer.Conv2d(16, 3, padding=1)
        self.bn1 = layer.BatchNorm2d()
        self.conv2 = layer.Conv2d(16, 3, padding=1)
        self.relu = layer.ReLU()
        self.flat = layer.Flatten()
        self.fc = layer.Linear(5)

    def forward(self, x):
        h = self.relu(self.bn1(self.conv1(x)))
        h = self.relu(self.conv2(h))
        return self.fc(self.flat(h))

    train_one_batch = TuneNet.train_one_batch


def test_remat_config_strictly_lowers_peak_bytes():
    def factory():
        dev = device.get_default_device()
        dev.SetRandSeed(11)
        return DeepNet(), opt.SGD(lr=0.1, momentum=0.9)

    def inputs():
        rs = np.random.RandomState(0)
        x = tensor.from_numpy(
            rs.randn(16, 3, 16, 16).astype(np.float32))
        y = tensor.from_numpy(rs.randint(0, 5, 16).astype(np.int32))
        return [x, y]

    sc = tuning.CostModelScorer(factory, inputs, chip="v5e")
    default = sc.score({"grad_accum": 2})
    remat = sc.score({"grad_accum": 2,
                      "remat_policy": "dots_saveable"})
    assert 0 < remat["peak_bytes"] < default["peak_bytes"], (
        remat["peak_bytes"], default["peak_bytes"])


def test_infeasible_peak_is_excluded():
    tight = dict(tuning.CHIP_SPECS["v5e"], hbm_bytes=1.0)
    sc = _scorer()
    sc.chip = "tight"
    try:
        tuning.CHIP_SPECS["tight"] = tight
        row = sc.score({})
        assert row["feasible"] is False
        assert row["score"] == float("-inf")
        assert tuning.tuning_stats().infeasible >= 1
    finally:
        del tuning.CHIP_SPECS["tight"]


# ---------------------------------------------------------------------------
# score cache
# ---------------------------------------------------------------------------
def test_score_cache_hit_on_unchanged_config():
    sc = _scorer()
    stats.reset_cache_stats()
    first = sc.score({"slot_dtype": "bfloat16"})
    again = sc.score({"slot_dtype": "bfloat16"})
    assert first["cached"] is False and again["cached"] is True
    assert again["score"] == first["score"]
    # HLO-neutral knobs (xla profile, pallas blocks) share the
    # measurement: no second lowering
    neutral = sc.score({"slot_dtype": "bfloat16",
                        "xla_profile": "latency",
                        "pallas_attn_tq": 256})
    assert neutral["cached"] is True
    ts = stats.cache_stats()["tuning"]
    assert ts["scored"] == 1 and ts["score_cache_hits"] == 2


# ---------------------------------------------------------------------------
# persisted store round trip
# ---------------------------------------------------------------------------
def test_store_round_trip(tmp_path):
    path = str(tmp_path / "tuned.json")
    store = tuning.TunedStore(path)
    cfg = {"slot_dtype": "bfloat16", "grad_accum": 2}
    store.put("fp-abc", "v5e", cfg, 123.4,
              provenance={"source": "cost-model"}, alias="tiny")
    # by fingerprint+chip, by fingerprint (any chip), by alias
    for got in (store.get(fingerprint="fp-abc", chip="v5e"),
                store.get(fingerprint="fp-abc"),
                store.get(alias="tiny")):
        assert got is not None
        assert got["config"] == tuning.validate_config(cfg)
        assert got["score"] == 123.4
        assert got["provenance"]["source"] == "cost-model"
    assert store.get(fingerprint="fp-abc", chip="v4") is None
    assert store.get(alias="nope") is None
    # overwrite wins; the file stays valid JSON (atomic replace)
    store.put("fp-abc", "v5e", {"grad_accum": 4}, 200.0, alias="tiny")
    assert store.get(alias="tiny")["config"]["grad_accum"] == 4
    json.load(open(path))
    # alias lists: every name resolves to the same fingerprint (the
    # resnet-18/resnet granularity pair a caller resolves by)
    store.put("fp-r", "v5e", {}, 1.0, alias=["resnet-18", "resnet"])
    assert store.get(alias="resnet")["fingerprint"] == "fp-r"
    assert store.get(alias="resnet-18")["fingerprint"] == "fp-r"


def test_corrupt_store_reads_empty_never_crashes(tmp_path, capsys):
    path = str(tmp_path / "tuned.json")
    open(path, "w").write("{not json")
    store = tuning.TunedStore(path)
    assert store.get(alias="x") is None
    assert "unreadable" in capsys.readouterr().err
    # and a put over the corpse recovers the store
    store.put("fp", "v5e", {}, 1.0, alias="x")
    assert store.get(alias="x") is not None


def test_unknown_device_kind_has_no_chip_spec():
    """A device kind with no CHIP_SPECS row is an error, never an
    assumed v5e; the kinds jax reports for known chips resolve."""
    assert tuning.normalize_chip("TPU v5 lite") == "v5e"
    assert tuning.normalize_chip("tpu TPU v5 lite") == "v5e"
    assert tuning.normalize_chip("cpu cpu") == "cpu"
    with pytest.raises(ValueError, match="no CHIP_SPECS row"):
        tuning.normalize_chip("TPU v9 mega")
    with pytest.raises(ValueError, match="no CHIP_SPECS row"):
        tuning.normalize_chip("")


def test_load_best_resolves_current_chip(tmp_path, monkeypatch):
    path = str(tmp_path / "tuned.json")
    monkeypatch.setenv("SINGA_TPU_TUNED_STORE", path)
    assert tuning.default_store_path() == path
    tuning.TunedStore(path).put("fp-x", "cpu", {"grad_accum": 2},
                                9.0, alias="m")
    ent = tuning.load_best(alias="m", chip="cpu", store_path=path)
    assert ent["config"]["grad_accum"] == 2


# ---------------------------------------------------------------------------
# measured score sources
# ---------------------------------------------------------------------------
def test_measured_score_overrides_model_on_exact_match():
    ms = tuning.MeasuredScores()
    cfg = tuning.validate_config({"slot_dtype": "bfloat16"})
    ms.add_config(cfg, 4321.0)
    sc = _scorer(measured=ms)
    row = sc.score(cfg)
    assert row["source"] == "measured" and row["score"] == 4321.0
    near = sc.score({"slot_dtype": "float16"})  # near-miss: no match
    assert near["source"] == "cost-model"
    assert stats.cache_stats()["tuning"]["measured_hits"] >= 1


def test_ingest_pallas_jsonl_and_snap(tmp_path):
    p = tmp_path / "sweep.jsonl"
    rows = [
        {"case": "attn512", "knob": "SINGA_TPU_ATTN_TQ",
         "value": 64, "us": 90.0, "us_ref": 100.0},
        {"case": "attn512", "knob": "SINGA_TPU_ATTN_TQ",
         "value": 128, "us": 70.0, "us_ref": 100.0},
        {"case": "attn512", "knob": "SINGA_TPU_ATTN_TQ",
         "value": 256, "us": 80.0, "us_ref": 100.0},
    ]
    body = "\n".join(json.dumps(r) for r in rows)
    p.write_text(body + "\n" + '{"case": "attn512", "kn')  # killed
    ms = tuning.ingest_pallas_jsonl(str(p))
    assert ms.pallas_knobs_swept() == ["pallas_attn_tq"]
    assert ms.best_pallas_value("pallas_attn_tq") == 128
    # proposals snap default pallas positions to the measured best
    picks = tuning.propose(budget=4, seed=0, measured=ms)
    assert picks[0]["pallas_attn_tq"] == 128
    # a missing file is an empty source, not an error
    assert tuning.ingest_pallas_jsonl(
        str(tmp_path / "nope.jsonl")).pallas_knobs_swept() == []


def test_ingest_metrics_jsonl(tmp_path):
    p = tmp_path / "metrics.jsonl"
    cfg = tuning.validate_config({"grad_accum": 2})
    recs = [
        {"config": cfg, "measured_examples_per_sec": 777.0,
         "source": "measured", "chip": "v5e", "batch": 256},
        {"step": 1, "loss": 0.5},                   # no config: skip
        {"config": {"bogus": 1}, "examples_per_sec": 1.0,
         "source": "measured"},                     # foreign: skip
    ]
    p.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    ms = tuning.ingest_metrics_jsonl(str(p))
    assert ms.lookup(cfg) == 777.0
    assert ms.lookup(tuning.default_config()) is None
    # chip/batch gates fail CLOSED: a CPU toy-geometry measurement
    # must never override a v5e candidate's modeled score
    assert tuning.ingest_metrics_jsonl(
        str(p), chip="cpu").lookup(cfg) is None
    assert tuning.ingest_metrics_jsonl(
        str(p), chip="v5e", batch=8).lookup(cfg) is None
    assert tuning.ingest_metrics_jsonl(
        str(p), chip="v5e", batch=256).lookup(cfg) == 777.0


def test_mixed_norm_raw_pallas_records_do_not_cross_rank():
    """Normalized (us/us_ref) and raw-microsecond sweep records rank
    in separate pools: a ~1.0 ratio must not beat a 50us raw time
    just because one record carried the XLA reference."""
    ms = tuning.MeasuredScores()
    ms.add_pallas("pallas_attn_tq", 64, 50.0)             # raw, fast
    ms.add_pallas("pallas_attn_tq", 128, 900.0, us_ref=3000.0)
    ms.add_pallas("pallas_attn_tq", 256, 400.0, us_ref=500.0)
    # normalized pool wins outright: 128 (0.3) beats 256 (0.8); the
    # raw 50us record cannot cross-rank into it
    assert ms.best_pallas_value("pallas_attn_tq") == 128
    raw_only = tuning.MeasuredScores()
    raw_only.add_pallas("pallas_attn_tq", 64, 50.0)
    raw_only.add_pallas("pallas_attn_tq", 128, 80.0)
    assert raw_only.best_pallas_value("pallas_attn_tq") == 64


# ---------------------------------------------------------------------------
# applying configs to the live process
# ---------------------------------------------------------------------------
def test_apply_config_arms_training_knobs():
    o = opt.SGD(lr=0.1)
    applied = tuning.apply_config(
        {"slot_dtype": "bfloat16", "grad_accum": 2,
         "remat_policy": "dots_saveable"}, optimizer=o)
    assert applied == {"slot_dtype": "bfloat16", "grad_accum": 2,
                       "remat_policy": "dots_saveable"}
    assert stats.grad_accum_n() == 2
    assert stats.remat_policy() == "dots_saveable"


def test_apply_config_serving_subset_skips_training_geometry():
    from singa_tpu.ops import pallas_kernels as pk

    saved_tq = pk._ATTN_TQ
    applied = tuning.apply_config(
        {"grad_accum": 2, "remat_policy": "dots_saveable",
         "bn_stats_dtype": "bfloat16", "pallas_attn_tq": 128},
        training=False)
    try:
        assert "grad_accum" not in applied
        assert "remat_policy" not in applied
        assert applied["bn_stats_dtype"] == "bfloat16"
        assert applied["pallas_attn_tq"] == 128
        assert os.environ.get("SINGA_TPU_ATTN_TQ") == "128"
        # the LIVE module global moves too — by apply time
        # pallas_kernels is already imported, so the env var alone
        # would be a silent no-op in this process
        assert pk._ATTN_TQ == 128
        assert stats.grad_accum_n() == 1
        assert stats.remat_policy() is None
    finally:
        os.environ.pop("SINGA_TPU_ATTN_TQ", None)
        pk._ATTN_TQ = saved_tq


# ---------------------------------------------------------------------------
# CLI smoke (the tier-1 CI gate: tiny model, <=8 candidates, CPU-only)
# ---------------------------------------------------------------------------
def test_cli_smoke_tiny_cnn(tmp_path):
    store = str(tmp_path / "store.json")
    jsonl = str(tmp_path / "search.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "autotune.py"),
         "--model", "tiny-cnn", "--budget", "8", "--seed", "0",
         "--platform", "cpu", "--store", store, "--jsonl", jsonl],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=_ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"] is True
    assert result["evaluated"] <= 8
    assert result["beats_default"] is True
    assert result["best_bytes"] < result["default_bytes"]
    # the winner persisted under its alias, loadable by the bench
    ent = tuning.TunedStore(store).get(alias="tiny-cnn")
    assert ent is not None
    assert ent["config"] == tuning.validate_config(result["best"])
    assert ent["provenance"]["seed"] == 0
    # the search JSONL parses one record per candidate
    lines = [json.loads(x) for x in open(jsonl) if x.strip()]
    assert len(lines) == result["evaluated"]
    assert lines[0]["config"] == tuning.default_config()


# ---------------------------------------------------------------------------
# Pallas CPU sweep -> autotuner round trip (satellite: the block-shape
# axis joins the search without a chip)
# ---------------------------------------------------------------------------
def test_pallas_tune_cpu_sweep_emits_ingestible_jsonl(tmp_path,
                                                      monkeypatch):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "pallas_tune_for_test",
        os.path.join(_ROOT, "benchmarks", "pallas_tune.py"))
    pt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pt)
    # one case, two values: the mechanics, not the full matrix
    monkeypatch.setattr(pt, "SWEEPS", [
        ("topk20", "SINGA_TPU_HIST_BUDGET", [1 << 11, 1 << 12])])
    jsonl = str(tmp_path / "sweep.jsonl")
    pt.main(["--cpu", "--jsonl", jsonl, "--deadline", "120"])
    rows = [json.loads(x) for x in open(jsonl) if x.strip()]
    assert len(rows) == 2
    assert all(r["mode"] == "cpu/interpret" for r in rows)
    assert all(r["us"] > 0 and r["us_ref"] > 0 for r in rows)
    ms = tuning.ingest_pallas_jsonl(jsonl)
    assert ms.pallas_knobs_swept() == ["pallas_hist_budget"]
    best = ms.best_pallas_value("pallas_hist_budget")
    assert best in (1 << 11, 1 << 12)
    # and the search snaps its candidates to the measured best
    picks = tuning.propose(budget=2, seed=0, measured=ms)
    assert picks[0]["pallas_hist_budget"] == best


# ---------------------------------------------------------------------------
# Multi-axis knobs (ISSUE 10): mesh geometry / pipeline microbatches /
# MoE capacity factor join the search space
# ---------------------------------------------------------------------------
def test_multi_axis_knobs_in_space():
    for knob in ("mesh_geometry", "pipeline_microbatches",
                 "moe_capacity_factor"):
        assert knob in tuning.KNOBS
        assert tuning.KNOBS[knob][0] is None  # default = off
        assert knob in tuning.HLO_KNOBS  # they change the traced HLO


MESH_SPACE = dict(
    SMALL_SPACE,
    slot_dtype=(None,),
    xla_profile=("default",),
    grad_accum=(1,),
    remat_policy=(None,),
    mesh_geometry=(None, "data=4,pipe=2"),
)


def test_mesh_geometry_flip_proposed_and_scored():
    """The acceptance loop (ISSUE 10): a multi-axis config (mesh
    flip) is PROPOSED by the single-flip sweep and SCORED end-to-end
    on the 8-virtual-device CPU mesh — feasible, finite score, the
    roofline normalized per device."""
    scorer = _scorer()
    result = tuning.autotune(scorer, budget=3, seed=0,
                             space=MESH_SPACE)
    rows = {r["config"]["mesh_geometry"]: r for r in result["rows"]}
    assert "data=4,pipe=2" in rows, "mesh flip never proposed"
    mesh_row = rows["data=4,pipe=2"]
    assert mesh_row["feasible"] is True
    assert np.isfinite(mesh_row["score"]) and mesh_row["score"] > 0
    assert mesh_row["n_devices"] == 8
    assert rows[None]["n_devices"] == 1


def test_infeasible_mesh_geometry_excluded():
    """A geometry whose axis product does not divide the available
    devices scores -inf with a loud reason instead of erroring (the
    shared-knob-space contract between 1-device CI and the mesh)."""
    scorer = _scorer()
    row = scorer._measure(dict(tuning.default_config(),
                               mesh_geometry="data=2,model=3"))
    assert row["feasible"] is False
    assert "devices" in row.get("reason", "")
    assert row["score"] == float("-inf")


def test_multi_axis_winner_persists_and_loads(tmp_path):
    """Winner with a mesh flip persists to the store and resolves by
    alias — how a later run takes the tuned config up."""
    scorer = _scorer()
    result = tuning.autotune(scorer, budget=3, seed=0,
                             space=MESH_SPACE)
    store = tuning.TunedStore(str(tmp_path / "tuned.json"))
    store.put(scorer.fingerprint, "v5e", result["best"],
              result["best_score"], alias=["autotune_net"])
    ent = store.get(alias="autotune_net", chip="v5e")
    assert ent is not None
    cfg = tuning.validate_config(ent["config"])
    assert cfg["mesh_geometry"] in (None, "data=4,pipe=2")


def test_apply_config_arms_parallel_knobs():
    from singa_tpu.parallel import plan as plan_mod

    applied = tuning.apply_config(
        {"mesh_geometry": "data=4,pipe=2",
         "pipeline_microbatches": 4, "moe_capacity_factor": 1.5})
    try:
        assert applied["mesh_geometry"] == "data=4,pipe=2"
        assert applied["pipeline_microbatches"] == 4
        assert applied["moe_capacity_factor"] == 1.5
        plan = plan_mod.process_plan()
        assert plan is not None and plan.axes["pipe"] == 2
        assert stats.get_config()["pipeline_microbatches"] == 4
        assert stats.get_config()["moe_capacity_factor"] == 1.5
        # the serving subset never arms training geometry
        applied_s = tuning.apply_config(
            {"mesh_geometry": "data=4,pipe=2"}, training=False)
        assert "mesh_geometry" not in applied_s
    finally:
        device.set_parallel_plan(None)
        stats.configure(pipeline_microbatches=None,
                        moe_capacity_factor=None)
