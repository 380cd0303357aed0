"""The operator tools under `tools/` and the one rule on where the
compile cache lives, on the CPU backend.

Each tool is loaded from its file (they are scripts, not a package's
modules) and driven through its `main` / `probe` on streams the test
itself writes under `tmp_path`: nothing here reads or writes a file of
the checkout.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load_module(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the compile cache: one rule (device.compile_cache_dir)
# ---------------------------------------------------------------------------
def test_one_rule_says_where_the_compile_cache_lives(monkeypatch):
    """`device.compile_cache_dir`: an exported
    JAX_COMPILATION_CACHE_DIR stands and code sets no directory;
    otherwise it is <checkout>/.jax_cache — never a temporary name, a
    pid or a time. `use_compile_cache` applies it in-process. (The
    chip machine exports the variable: PERF.md §6, PR 22.)"""
    import jax

    from singa_tpu import device

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    default = os.path.join(_ROOT, ".jax_cache")
    assert device.compile_cache_dir() == default
    before = jax.config.jax_compilation_cache_dir
    try:
        assert device.use_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
        # an exported directory wins, and code then sets none
        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert device.compile_cache_dir() == "/some/dir"
        assert device.use_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # no cache path in an entry point that compiles on the chip is
    # built from a temporary name, a pid or a time
    for rel in ("chip_smoke.py", "perfbench/run.py", "singa_tpu/device.py",
                "examples/cnn/benchmark.py"):
        text = open(os.path.join(_ROOT, rel)).read()
        assert "tempfile" not in text and "mkdtemp" not in text, rel


def test_exported_cache_dir_gets_the_entries(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR exported, a process that calls
    `device.use_compile_cache()` caches THERE, and nothing is created
    or added under <checkout>/.jax_cache."""
    default = os.path.join(_ROOT, ".jax_cache")
    before = sorted(os.listdir(default)) if os.path.isdir(default) \
        else None
    elsewhere = tmp_path / "elsewhere"
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax, jax.numpy as jnp\n"
         "from singa_tpu import device\n"
         "print(device.use_compile_cache())\n"
         "jax.jit(lambda x: x @ x + 1)(jnp.ones((64, 64)))"
         ".block_until_ready()\n"],
        capture_output=True, text=True, timeout=120, cwd=_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(elsewhere),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(elsewhere)
    assert os.listdir(elsewhere), "no cache entry where the env said"
    after = sorted(os.listdir(default)) if os.path.isdir(default) \
        else None
    assert after == before


# ---------------------------------------------------------------------------
# tools/metrics_lint.py
# ---------------------------------------------------------------------------
def test_metrics_lint_passes_what_the_writers_write(tmp_path):
    """Every stream the program writes lints clean against the
    schema-version registry: a decode engine's per-dispatch records
    through `MetricsLogger` (with the decode tier's `extra` fields),
    a trainer-style record, and the SLO engine's alert transitions."""
    from singa_tpu import device, serve, slo, stats, tensor, trace
    from singa_tpu.models.transformer import TransformerLM

    lint = _load_module("metrics_lint_for_test", "tools/metrics_lint.py")
    dev = device.get_default_device()
    dev.SetRandSeed(0)
    m = TransformerLM(64, d_model=32, num_heads=2, num_layers=2,
                      max_len=16)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32), device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    saved = serve.get_decode_config()
    done = stats.decode_stats().completed   # one count a process
    dpath = tmp_path / "decode.jsonl"
    mlog = trace.MetricsLogger(str(dpath))
    rs = np.random.RandomState(3)
    try:
        with serve.ServingEngine(m, max_sessions=4, max_new_tokens=4,
                                 metrics=mlog) as eng:
            replies = [eng.submit_decode(
                rs.randint(0, 64, (1, 3)).astype(np.int32), 4)
                for _ in range(3)]
            for r in replies:
                r.result(timeout=120)
    finally:
        device.set_decode_serving(**saved)
        mlog.close()
    recs = trace.read_metrics(str(dpath))
    assert recs, "the decode engine wrote no metrics records"
    x = recs[-1]["extra"]
    assert x["tier"] == "decode"
    for k in ("sessions", "slots", "block", "slab_seq", "occupancy",
              "queue_depth", "tokens_streamed", "completed", "expired",
              "shed", "failed"):
        assert k in x, f"decode metrics record missing extra.{k}"
    assert recs[-1]["extra"]["completed"] == done + 3

    tpath = tmp_path / "train.jsonl"
    tlog = trace.MetricsLogger(str(tpath))
    tlog.log_step(1, loss=0.5, examples=8, step_s=0.1, lr=0.01)
    tlog.close()

    apath = tmp_path / "alerts.jsonl"
    slo.configure(True, window_scale=1.0, spec={"availability": 0.999},
                  alerts_path=str(apath))
    try:
        for i in range(100):
            slo.observe_outcome(False, now=1000.0 + i * 0.1)
        slo.tick(now=1010.0)
        slo.tick(now=1220.0)
    finally:
        slo.configure(False)
    assert apath.read_text().strip(), "no alert transition was written"

    for path, family in ((dpath, "metrics"), (tpath, "metrics"),
                         (apath, "alerts")):
        issues, n, fam = lint.lint_file(str(path))
        assert issues == [] and n >= 1 and fam == family, (path, issues)
    assert lint.main([str(dpath), str(tpath), str(apath)]) == 0
    assert lint.main(["--dir", str(tmp_path)]) == 0


def test_metrics_lint_catches_drift(tmp_path):
    """The lint is not a rubber stamp: unknown keys (grown without a
    schema bump), mixed writer vintages, and mid-stream garbage all
    fail; the at-most-one torn TRAILING line a SIGKILL leaves is
    tolerated by design, and non-telemetry JSONL is skipped, not
    failed."""
    lint = _load_module("metrics_lint_for_test2",
                        "tools/metrics_lint.py")
    v2 = {"schema": 2, "time": 1.0, "step": 1, "loss": 0.5,
          "step_s": 0.1, "data_wait_s": None, "dispatch_s": None,
          "device_sync_s": None, "examples_per_sec": 10.0,
          "cache": {}, "resilience": {}, "accum": {}, "metrics": {},
          "extra": {}, "pid": 1, "mono": 0.5}
    alert = {"schema": 1, "kind": "slo_alert", "time": 1.0,
             "mono": 0.5, "alert": "availability", "rule": "fast",
             "severity": "page", "replica": "-", "state": "pending",
             "episode": 1, "burn_long": 9.0, "burn_short": 9.0,
             "value": 9.0, "threshold": 14.4}

    clean = tmp_path / "clean.jsonl"
    clean.write_text(json.dumps(v2) + "\n" + json.dumps(alert)[:20])
    issues, n, family = lint.lint_file(str(clean))
    assert issues == [] and n == 1 and family == "metrics", (
        "torn trailing line must be tolerated")

    grown = tmp_path / "grown.jsonl"
    grown.write_text(json.dumps(dict(v2, surprise=1)) + "\n")
    issues, _, _ = lint.lint_file(str(grown))
    assert any("surprise" in i and "bump the version" in i
               for i in issues)
    assert lint.main([str(grown)]) == 1

    mixed = tmp_path / "mixed.jsonl"
    mixed.write_text(json.dumps(v2) + "\n"
                     + json.dumps(dict(v2, schema=1)) + "\n")
    issues, _, _ = lint.lint_file(str(mixed))
    assert any("mixed schema" in i for i in issues)

    torn = tmp_path / "torn.jsonl"
    torn.write_text('{"garbage\n' + json.dumps(v2) + "\n")
    issues, _, _ = lint.lint_file(str(torn))
    assert any("torn mid-stream" in i for i in issues)

    alerts = tmp_path / "alerts.jsonl"
    alerts.write_text(json.dumps(alert) + "\n")
    issues, n, family = lint.lint_file(str(alerts))
    assert issues == [] and family == "alerts"
    missing = tmp_path / "missing.jsonl"
    missing.write_text(json.dumps(
        {k: v for k, v in alert.items() if k != "burn_long"}) + "\n")
    issues, _, _ = lint.lint_file(str(missing))
    assert any("missing key" in i and "burn_long" in i
               for i in issues)

    other = tmp_path / "other.jsonl"
    other.write_text(json.dumps({"fingerprint": "abc"}) + "\n")
    issues, n, family = lint.lint_file(str(other))
    assert issues == [] and family is None  # skipped, not failed


# ---------------------------------------------------------------------------
# tools/serve_health.py, tools/fleet_top.py: the SLO alert blocks
# ---------------------------------------------------------------------------
def test_serve_health_folds_alert_severity(tmp_path):
    """A health snapshot carrying the SLO alert-counts block renders
    `alerts[...]` and the WORST firing severity folds into the exit
    code (page => 2/unhealthy, ticket => 1/degraded); a snapshot
    WITHOUT the block renders as it did before the block existed
    (append-only probe contract, same discipline as decode[...])."""
    sh = _load_module("serve_health_for_slo_test",
                      "tools/serve_health.py")
    base = {"state": "ready", "pid": 123, "queue_depth": 0, "shed": 2}
    old = tmp_path / "old.health.json"
    old.write_text(json.dumps(base))
    code_old, line_old = sh.probe(str(old))
    assert code_old == 0 and "alerts[" not in line_old
    quiet = tmp_path / "quiet.health.json"
    quiet.write_text(json.dumps(dict(base, alerts={
        "pending": 0, "firing": 0, "page": 0, "ticket": 0})))
    code, line = sh.probe(str(quiet))
    assert code == 0 and "alerts[firing=0 pending=0]" in line
    assert line.startswith(line_old)  # append-only
    ticket = tmp_path / "ticket.health.json"
    ticket.write_text(json.dumps(dict(base, alerts={
        "pending": 0, "firing": 1, "page": 0, "ticket": 1})))
    assert sh.probe(str(ticket))[0] == 1
    page = tmp_path / "page.health.json"
    page.write_text(json.dumps(dict(base, alerts={
        "pending": 1, "firing": 2, "page": 1, "ticket": 1})))
    assert sh.probe(str(page))[0] == 2


def test_fleet_top_alert_panel_and_follow(tmp_path, capsys):
    """fleet_top's alert panel (state replayed from the alerts JSONL,
    active alerts listed firing-first) and its --follow mode;
    --iterations 1 bounds a follow pass."""
    ft = _load_module("fleet_top_slo_test", "tools/fleet_top.py")
    with open(tmp_path / "fleet.jsonl", "w") as f:
        f.write(json.dumps({"time": 1.0, "step": 1, "extra": {
            "event": "route", "fleet_requests": 4,
            "fleet_replies": 4, "routed": 4}}) + "\n")
    rec = {"schema": 1, "kind": "slo_alert", "time": 1.0, "mono": 0.5,
           "alert": "availability", "rule": "fast",
           "severity": "page", "replica": "-", "state": "pending",
           "episode": 1, "burn_long": 99.0, "burn_short": 99.0,
           "value": 99.0, "threshold": 14.4}
    with open(tmp_path / "fleet_alerts.jsonl", "w") as f:
        f.write(json.dumps(rec) + "\n")
        f.write(json.dumps(dict(rec, time=2.0, state="firing"))
                + "\n")
        f.write(json.dumps(dict(
            rec, time=2.5, alert="anomaly:hb_gap", rule="-",
            replica="w1", state="firing")) + "\n")
    rc = ft.main(["--dir", str(tmp_path), "--follow",
                  "--iterations", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "alerts: firing 2" in out
    assert "availability" in out and "anomaly:hb_gap" in out
    assert "w1" in out
    # structured counts ride --json for scrapers
    rc = ft.main(["--dir", str(tmp_path), "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    j = json.loads(out)
    assert j["alerts"]["firing"] == 2
    assert j["alerts"]["transitions"] == 3
