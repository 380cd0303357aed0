"""bench.py mechanics on the CPU backend (BENCH_PLATFORM=cpu).

Nothing else in the suite exercises bench.py importing, parsing args,
and running stages. These tests pin the subprocess contract its
parent and tools/fold_onchip.py rely on: one parseable result-JSON
line on stdout, ok flag, rc 0.
"""
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_stage(args, timeout=240, extra_env=None):
    env = dict(os.environ, BENCH_PLATFORM="cpu", **(extra_env or {}))
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")] + args,
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=_ROOT,
    )
    last = None
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            last = json.loads(line)
    return proc, last


def _load_module(name, relpath):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_run_stage_deadline_kill_returns_none():
    """A stage killed at its deadline yields no result — never a
    partial or stored one."""
    bench = _load_module("bench_for_test", "bench.py")
    assert bench.run_stage("probe", [], 0.2) is None


def test_no_tpu_is_a_failed_run_with_nothing_to_reemit():
    """`python bench.py` without a TPU exits non-zero and prints no
    result: the stored-number re-emission, its file and the probe
    escalation ladder are gone, and an unknown device kind has no
    peak (never an assumed v5e)."""
    src = open(os.path.join(_ROOT, "bench.py")).read()
    for gone in ("LASTGOOD", "_ESCALATION", "probe_timeouts",
                 "tpu_unreachable", "assumed-v5e"):
        assert gone not in src, gone
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")],
        capture_output=True, text=True, timeout=240, cwd=_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_DEADLINE="200"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
    bench = _load_module("bench_for_test", "bench.py")
    assert bench._chip_peak("TPU v5 lite")[0] == 197e12
    with pytest.raises(ValueError, match="no peak"):
        bench._chip_peak("TPU v9 mega")
    with pytest.raises(ValueError, match="no peak"):
        bench._chip_peak("cpu")


def test_fold_onchip_renders_driver_table(tmp_path, capsys,
                                          monkeypatch):
    """tools/fold_onchip.py renders the driver-level result table."""
    fold = _load_module("fold_onchip_for_test", "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    (logs / "driver.log").write_text(json.dumps(
        {"metric": "resnet50_images_per_sec_chip", "value": 123.4,
         "unit": "img/s"}) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    assert "123.4 img/s" in capsys.readouterr().out


def test_fold_onchip_renders_stage_seconds(tmp_path, capsys,
                                           monkeypatch):
    """ISSUE 5: tools/fold_onchip.py renders the `stage_seconds`
    breakdown column on throughput rows; pre-observability logs
    (no field) fold unchanged."""
    fold = _load_module("fold_onchip_for_test", "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    (logs / "resnet_bs128.out").write_text(json.dumps(
        {"ok": True, "ips": 1234.5, "step_ms": 103.7, "batch": 128,
         "precision": "bf16",
         "stage_seconds": {"setup": 3.1, "compile": 41.0,
                           "steady": 12.5}}) + "\n")
    (logs / "resnet_old.out").write_text(json.dumps(
        {"ok": True, "ips": 900.0, "step_ms": 142.2, "batch": 128,
         "precision": "bf16"}) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "t=setup 3.1s/compile 41.0s/steady 12.5s" in out
    assert "900.0 img/s" in out and "t=setup" not in \
        [ln for ln in out.splitlines() if "900.0" in ln][0]


def test_fold_onchip_renders_compile_split_and_warm_column(
        tmp_path, capsys, monkeypatch):
    """ISSUE 6: when a stage reports the trace/compile/load split and
    the artifact-cache counters, tools/fold_onchip.py renders them
    (plus the `warm=` hit-rate column); pre-split logs fold with the
    ISSUE 5 three-field rendering unchanged (pinned by
    test_fold_onchip_renders_stage_seconds)."""
    fold = _load_module("fold_onchip_for_test", "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    (logs / "resnet_warm.out").write_text(json.dumps(
        {"ok": True, "ips": 2000.0, "step_ms": 64.0, "batch": 128,
         "precision": "bf16",
         "stage_seconds": {"setup": 3.0, "trace": 1.2, "compile": 8.4,
                           "load": 0.05, "steady": 12.5},
         "export_cache": {"hits": 2, "misses": 0,
                          "hit_rate": 1.0}}) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert ("t=setup 3.0s/trace 1.2s/compile 8.4s/load 0.05s"
            "/steady 12.5s") in out
    assert "warm=100%" in out


def test_stage_env_exports_compilation_cache(monkeypatch):
    """ONE rule says where the persistent compile cache lives
    (`device.compile_cache_dir`): an exported JAX_COMPILATION_CACHE_DIR
    stands and code sets no directory; otherwise it is
    <checkout>/.jax_cache — never a temporary name, a pid or a time.
    bench.py's stage env carries that directory to every descendant
    (stage_pallas / stage_parity spawn grandchildren that never run
    _setup_jax), and `use_compile_cache` applies it in-process."""
    import jax

    from singa_tpu import device

    bench = _load_module("bench_for_test", "bench.py")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("SINGA_TPU_EXPORT_CACHE", raising=False)
    default = os.path.join(_ROOT, ".jax_cache")
    assert device.compile_cache_dir() == default
    assert bench._stage_env()["JAX_COMPILATION_CACHE_DIR"] == default
    before = jax.config.jax_compilation_cache_dir
    try:
        assert device.use_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
        # an exported directory wins, and code then sets none
        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert device.use_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before
        assert bench._stage_env()[
            "JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # ISSUE 6: the AOT artifact store travels the same way (kill the
    # trace half of a repeat attempt, not just the compile half)
    assert bench._stage_env()["SINGA_TPU_EXPORT_CACHE"].endswith(
        ".export_cache")
    # and run_stage actually passes the env to the child
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert "env=_stage_env()" in src
    # no cache path in code is built from a temporary name, a pid or
    # a time (benchmarks/eager_overhead.py's --cpu cold/warm
    # experiment excepted, and unreachable from the chip path)
    for rel in ("bench.py", "chip_smoke.py", "singa_tpu/device.py",
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


def test_resnet_accum_matrix_is_queued_and_validated():
    """ISSUE 4: the effective-batch-512 accumulation rows ride the
    driver ramp (x4 and x2), and an indivisible --batch/--accum pair
    dies loudly before measuring the wrong thing."""
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert '"--accum", "4"' in src and '"--accum", "2"' in src
    assert "run_resnet(512" in src
    proc, result = _run_stage(
        ["--stage", "resnet", "--batch", "8", "--accum", "3",
         "--steps", "1", "--deadline", "60"], timeout=240)
    assert result is not None and result["ok"] is False
    assert "not divisible" in result["error"]


def test_probe_stage_contract():
    proc, result = _run_stage(["--stage", "probe"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None, "no JSON result line on stdout"
    assert result["ok"] is True
    assert result["platform"] == "cpu"


def test_unknown_flag_is_loud():
    proc, _ = _run_stage(["--stage", "probe", "--bogus-flag"])
    assert proc.returncode != 0, (
        "unknown flags must fail loudly, not measure the wrong thing")


def test_bert_stage_contract_and_slot_dtype_matrix():
    """The BERT-SONNX fine-tune stage (north-star config #5's chip
    metric): one result-JSON line with the pinned metric name, and the
    `--slot-dtype` matrix column carried in the result so
    tools/fold_onchip.py folds matrix rows without format drift.
    ISSUE 5: the result also carries the `stage_seconds` wall-time
    breakdown and the stage's metrics-JSONL path, and that JSONL
    parses with one record per measured block."""
    proc, result = _run_stage(
        ["--stage", "bert", "--size", "tiny", "--batch", "2",
         "--seq", "16", "--steps", "2", "--deadline", "150",
         "--slot-dtype", "bfloat16"], timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None, "no JSON result line on stdout"
    assert result["ok"] is True
    assert result["metric"] == "bert_finetune_tokens_per_sec"
    assert result["tokens_per_sec"] > 0
    assert result["step_ms"] > 0
    assert result["slot_dtype"] == "bfloat16"
    # observability contract (ISSUE 5; ISSUE 6 splits `compile` into
    # trace/compile/load and adds the artifact-cache hit rate)
    assert set(result["stage_seconds"]) == {"setup", "trace",
                                            "compile", "load",
                                            "steady"}
    assert all(v >= 0 for v in result["stage_seconds"].values())
    ec = result["export_cache"]
    assert set(ec) == {"hits", "misses", "hit_rate"}
    assert 0.0 <= ec["hit_rate"] <= 1.0
    assert result["metrics_jsonl"] == os.path.join("metrics",
                                                   "bench_bert.jsonl")
    from singa_tpu import trace

    recs = trace.read_metrics(
        os.path.join(_ROOT, result["metrics_jsonl"]))
    assert recs, "bert stage wrote no metrics records"
    last = recs[-1]
    assert last["examples_per_sec"] > 0 and isinstance(
        last["loss"], float)


def test_serve_stage_contract_and_acceptance():
    """ISSUE 7: the continuous-batching serve stage's JSON contract —
    pinned field set, >= 3x requests/sec over the batch=1 sequential
    baseline under the same Poisson load (the acceptance gate, CPU-
    measurable by design), per-request replies bit-identical to the
    unbatched forward (dyadic arithmetic), and forward traces bounded
    by the bucket count. The metrics JSONL parses with one record per
    dispatch carrying the occupancy/pad/percentile fields."""
    proc, result = _run_stage(
        ["--stage", "serve", "--requests", "300",
         "--deadline", "150", "--chaos"], timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None, "no JSON result line on stdout"
    assert result["ok"] is True
    assert result["metric"] == "serve_requests_per_sec"
    for k in ("serve_requests_per_sec", "sequential_requests_per_sec",
              "speedup_vs_sequential", "p50_ms", "p95_ms", "p99_ms",
              "sequential_p50_ms", "sequential_p99_ms", "dispatches",
              "coalesce_mean", "occupancy_mean", "pad_fraction_mean",
              "buckets", "replies_match", "forward_traces",
              "n_buckets", "retrace_bound_ok", "stage_seconds",
              "export_cache", "metrics_jsonl"):
        assert k in result, f"serve result missing {k}"
    assert result["serve_requests_per_sec"] > 0
    assert result["speedup_vs_sequential"] >= 3.0, (
        f"continuous batching only "
        f"{result['speedup_vs_sequential']}x vs sequential")
    assert result["replies_match"] is True
    assert result["forward_traces"] <= result["n_buckets"]
    assert result["retrace_bound_ok"] is True
    assert 0.0 < result["occupancy_mean"] <= 1.0
    assert result["dispatches"] < result["requests"], (
        "no coalescing happened: one dispatch per request")
    assert result["p50_ms"] <= result["p99_ms"]
    assert result["metrics_jsonl"] == os.path.join(
        "metrics", "bench_serve.jsonl")
    from singa_tpu import trace

    recs = trace.read_metrics(
        os.path.join(_ROOT, result["metrics_jsonl"]))
    assert recs, "serve stage wrote no metrics records"
    x = recs[-1]["extra"]
    for k in ("requests", "rows", "bucket", "occupancy",
              "pad_fraction", "queue_depth", "p50_ms", "p99_ms",
              "expired", "shed", "retries", "failed"):
        assert k in x, f"serving metrics record missing extra.{k}"
    # ISSUE 8: the --chaos arm's contract — availability + SLO under
    # injected faults, counters that reconcile, and the same
    # bit-identity gate the clean arm pins
    c = result["chaos"]
    for k in ("availability_pct", "delivered", "failed", "p50_ms",
              "p99_ms", "replies_match", "retries",
              "dispatch_failures", "poisoned", "restarts",
              "counters_reconcile"):
        assert k in c, f"chaos sub-dict missing {k}"
    assert c["replies_match"] is True
    assert c["counters_reconcile"] is True
    assert c["dispatch_failures"] > 0, "chaos arm injected nothing"
    assert 0.0 < c["availability_pct"] <= 100.0


def test_serve_row_rides_the_driver_ramp():
    """The serving metric reaches the driver result table
    (`serve_requests_per_sec` in result_extra), same as lm/decode/
    bert."""
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert 'run_stage("serve"' in src
    assert 'result_extra["serve_requests_per_sec"]' in src


def test_fold_onchip_renders_serve_stage(tmp_path, capsys,
                                         monkeypatch):
    """ISSUE 7: tools/fold_onchip.py renders serve-stage rows
    (req/s, SLO percentiles, occupancy, speedup, warm column)."""
    fold = _load_module("fold_onchip_for_test", "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    (logs / "serve.out").write_text(json.dumps(
        {"ok": True, "metric": "serve_requests_per_sec",
         "serve_requests_per_sec": 8123.4, "p50_ms": 2.1,
         "p99_ms": 7.9, "occupancy_mean": 0.83,
         "speedup_vs_sequential": 4.4,
         "stage_seconds": {"setup": 2.0, "trace": 1.0, "compile": 0.5,
                           "load": 0.1, "steady": 3.0},
         "export_cache": {"hits": 7, "misses": 0,
                          "hit_rate": 1.0}}) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "8123.4 req/s" in out
    assert "p50 2.1 ms/p99 7.9 ms" in out
    assert "occ 0.83" in out and "x4.4 vs seq" in out
    assert "warm=100%" in out
    assert "chaos" not in out  # pre-chaos logs fold unchanged


def test_fold_onchip_renders_serve_chaos_arm(tmp_path, capsys,
                                             monkeypatch):
    """ISSUE 8: the bench `--chaos` arm (availability %, p99 under
    faults, retries) renders next to the clean serve numbers; a
    mismatch in either gate is flagged loudly."""
    fold = _load_module("fold_onchip_for_test", "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    row = {"ok": True, "metric": "serve_requests_per_sec",
           "serve_requests_per_sec": 8123.4, "p50_ms": 2.1,
           "p99_ms": 7.9, "occupancy_mean": 0.83,
           "speedup_vs_sequential": 4.4,
           "chaos": {"availability_pct": 98.75, "p99_ms": 12.3,
                     "retries": 7, "replies_match": True,
                     "counters_reconcile": True}}
    (logs / "serve.out").write_text(json.dumps(row) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "chaos: 98.75% avail, p99 12.3 ms, 7 retries" in out
    assert "MISMATCH" not in out
    # a failed bit-identity or reconciliation gate is loud
    row["chaos"]["replies_match"] = False
    (logs / "serve.out").write_text(json.dumps(row) + "\n")
    assert fold.main() == 0
    assert "MISMATCH" in capsys.readouterr().out


def test_serve_decode_stage_contract_and_acceptance():
    """ISSUE 16: the continuous-batching decode stage's JSON
    contract — pinned field set, >= 2x decode tokens/sec over the
    sequential per-request generate() baseline under the same seeded
    Poisson schedule (the acceptance gate, CPU-measurable by design:
    a decode step is memory-bound, so fusing sessions amortizes the
    param stream on every backend), token streams bit-identical to
    generate() on every pass, TTFT/TPOT percentiles decoded from the
    PR 15 trace segments, and the 4-equation session reconciliation
    exact at quiescence. The --chaos arm keeps delivered streams
    bit-exact under injected prefill/decode faults."""
    proc, result = _run_stage(
        ["--stage", "serve-decode", "--requests", "64",
         "--deadline", "240", "--chaos"], timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None, "no JSON result line on stdout"
    assert result["ok"] is True
    assert result["metric"] == "serve_decode_tokens_per_sec"
    for k in ("serve_decode_tokens_per_sec",
              "sequential_tokens_per_sec", "speedup_vs_sequential",
              "ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
              "tpot_p99_ms", "slo_segments", "streams_match",
              "tokens_exact", "counters_reconcile", "decode_steps",
              "prefills", "occupancy_mean", "slots", "decode_block",
              "warmed_executables", "stage_seconds", "export_cache",
              "metrics_jsonl"):
        assert k in result, f"serve-decode result missing {k}"
    assert result["serve_decode_tokens_per_sec"] > 0
    # Quiet-box runs measure 2.0-3.1x, but tier-1 shares one CPU core
    # with the rest of the suite: the engine arm pays thread
    # context-switch tax the single-threaded sequential baseline never
    # does, and a lucky-fast sequential pass squeezes the ratio (1.81x
    # observed under load). The >= 2x acceptance gate proper lives in
    # the slow-tier test below and in the committed bench fixture +
    # driver ramp row; this floor only catches a real regression
    # (batching slower than, or barely above, sequential).
    assert result["speedup_vs_sequential"] >= 1.4, (
        f"continuous batching only "
        f"{result['speedup_vs_sequential']}x vs sequential generate")
    assert result["streams_match"] is True
    assert result["tokens_exact"] is True
    assert result["counters_reconcile"] is True
    assert 0.0 < result["occupancy_mean"] <= 1.0
    assert result["warmed_executables"] > 0
    assert result["slo_segments"]["ttft"]["count"] > 0
    assert result["ttft_p50_ms"] <= result["ttft_p99_ms"]
    assert result["metrics_jsonl"] == os.path.join(
        "metrics", "bench_serve_decode.jsonl")
    from singa_tpu import trace

    recs = trace.read_metrics(
        os.path.join(_ROOT, result["metrics_jsonl"]))
    assert recs, "serve-decode stage wrote no metrics records"
    x = recs[-1]["extra"]
    for k in ("tier", "sessions", "slots", "block", "slab_seq",
              "occupancy", "queue_depth", "tokens_streamed",
              "completed", "expired", "shed", "failed"):
        assert k in x, f"decode metrics record missing extra.{k}"
    assert x["tier"] == "decode"
    c = result["chaos"]
    for k in ("availability_pct", "delivered", "failed", "refused",
              "streams_match", "counters_reconcile"):
        assert k in c, f"chaos sub-dict missing {k}"
    assert c["streams_match"] is True
    assert c["counters_reconcile"] is True
    assert 0.0 < c["availability_pct"] <= 100.0


@pytest.mark.slow
def test_serve_decode_acceptance_gate_two_x():
    """The ISSUE 16 acceptance gate at full strength: >= 2x decode
    tokens/sec over sequential generate(). Slow-tier because the
    measurement needs the box to itself — under tier-1's shared core
    the threaded engine arm is structurally taxed (see the 1.4x floor
    in the contract test above)."""
    proc, result = _run_stage(
        ["--stage", "serve-decode", "--requests", "64",
         "--deadline", "240"], timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["ok"] is True
    assert result["streams_match"] is True
    assert result["tokens_exact"] is True
    assert result["counters_reconcile"] is True
    assert result["speedup_vs_sequential"] >= 2.0, (
        f"continuous batching only "
        f"{result['speedup_vs_sequential']}x vs sequential generate")


def test_serve_decode_row_rides_the_driver_ramp():
    """The decode-serving metric reaches the driver result table
    (`serve_decode_tokens_per_sec` in result_extra) next to the
    decode and serve rows, and the decode stage's prompt/new
    geometry is driveable from the CLI (no hardcoded dispatch)."""
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert 'run_stage("serve-decode"' in src
    assert 'result_extra["serve_decode_tokens_per_sec"]' in src
    assert 'stage_decode(a.batch, a.prompt, a.new, a.deadline)' in src


def test_fold_onchip_renders_serve_decode_stage(tmp_path, capsys,
                                               monkeypatch):
    """ISSUE 16: tools/fold_onchip.py renders serve-decode rows
    (tok/s, speedup, TTFT/TPOT SLOs, occupancy, chaos arm) and flags
    a bit-identity or reconciliation break loudly; logs without the
    key fold unchanged."""
    fold = _load_module("fold_onchip_for_test", "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    row = {"ok": True, "metric": "serve_decode_tokens_per_sec",
           "serve_decode_tokens_per_sec": 1604.7,
           "speedup_vs_sequential": 2.65,
           "ttft_p50_ms": 15.9, "ttft_p99_ms": 25.2,
           "tpot_p99_ms": 92.9, "occupancy_mean": 0.9,
           "streams_match": True, "tokens_exact": True,
           "counters_reconcile": True,
           "chaos": {"availability_pct": 95.83, "failed": 1,
                     "streams_match": True,
                     "counters_reconcile": True}}
    (logs / "serve_decode.out").write_text(json.dumps(row) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "1605 tok/s" in out
    assert "x2.65 vs seq" in out
    assert "ttft p50 15.9 ms/p99 25.2 ms" in out
    assert "tpot p99 92.9 ms" in out
    assert "occ 0.9" in out
    assert "chaos: 95.83% avail, 1 failed" in out
    assert "MISMATCH" not in out
    row["streams_match"] = False
    (logs / "serve_decode.out").write_text(json.dumps(row) + "\n")
    assert fold.main() == 0
    assert "MISMATCH" in capsys.readouterr().out


def test_tpu_watch_decode_flavor():
    """tools/tpu_watch.sh grows a `decode` flavor rendering the
    decode tier's per-dispatch record (fused sessions/slots, run-
    ahead block, slab seq rung, occupancy, reconciliation counters);
    it must sit ABOVE the serve flavor, whose *serve*.jsonl glob
    would otherwise swallow bench_serve_decode.jsonl."""
    sh = open(os.path.join(_ROOT, "tools", "tpu_watch.sh")).read()
    dec = sh.index('"$1" = "decode"')
    srv = sh.index('"$1" = "serve"')
    assert dec < srv, "decode flavor must precede the serve glob"
    block = sh[dec:srv]
    for key in ("*decode*.jsonl", "sessions", "slots", "block",
                "slab_seq", "occupancy", "queue_depth",
                "tokens_streamed", "completed", "expired", "shed",
                "failed"):
        assert key in block, f"decode watch block missing {key}"
    # ISSUE 19: the quant column renders only when the record has it
    # (pre-19 and fp32 streams render byte-identically)
    assert 'x.get("quant")' in block


def test_fleet_decode_stage_contract_pins():
    """ISSUE 17: the fleet-decode stage's load-bearing mechanics,
    pinned at the source level (the full run lives in the slow tier —
    it needs the box to itself for an honest capacity ratio):
    dispatch branch + metric name, the >= 1.7x gate computed from the
    measured ratio, SIGKILLs DISCOVERED from worker exit codes (-9)
    rather than trusted from the injector, the burst gap sized off
    the FLEET's drain (replicas x the baseline's), the sampler pair
    warmed so no compile lands inside a sampled session's TTFT, and
    the stale-telemetry cleanup before the run."""
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert 'if a.stage == "fleet-decode":' in src
    assert "def stage_fleet_decode(" in src
    assert '"metric": "fleet_decode_tokens_per_sec"' in src
    assert '"speedup_gate_1p7x": bool(speedup >= 1.7)' in src
    assert 'g.get("exit_code") == -9' in src
    assert "8.0 * replicas * d_batch" in src
    assert 'samplers=[(0.7, 8)]' in src
    assert "bench_fleet_decode.jsonl" in src
    # the chaos arm waits for the supervisor to FINISH the respawns
    # before reading counters — stopping mid-respawn under-reports
    # `restarts` and strands a half-booted worker
    assert ">= len(kill_at)" in src
    # NOT on the chip ramp: its worker processes would be spawned by
    # a stage process that already holds the chip (one process per
    # chip; fleet_proc refuses) — it is a CPU mechanics stage
    assert 'run_stage("fleet-decode"' not in src


@pytest.mark.slow
def test_fleet_decode_acceptance_gate():
    """The ISSUE 17 acceptance at full strength: >= 1.7x aggregate
    decode tokens/sec over the 1-replica engine at 2 proc replicas
    under the same burst schedule, every delivered stream
    bit-identical, the 4-equation + transport reconciliation exact,
    and the chaos arm with >= 2 REAL SIGKILLs delivering zero torn
    tokens. Slow-tier: the capacity ratio needs the box to itself."""
    proc, result = _run_stage(
        ["--stage", "fleet-decode", "--requests", "48",
         "--deadline", "500", "--chaos"], timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None, "no JSON result line on stdout"
    assert result["ok"] is True
    assert result["metric"] == "fleet_decode_tokens_per_sec"
    for k in ("fleet_decode_tokens_per_sec", "baseline_tokens_per_sec",
              "speedup_vs_single_engine", "speedup_gate_1p7x",
              "streams_match", "counters_reconcile",
              "transport_reconcile", "ttft_p99_ms", "tpot_p99_ms",
              "slo_segments", "trace", "chaos"):
        assert k in result, f"fleet-decode result missing {k}"
    assert result["speedup_vs_single_engine"] >= 1.7, (
        f"fleet decode only {result['speedup_vs_single_engine']}x "
        "vs the single engine")
    assert result["speedup_gate_1p7x"] is True
    assert result["streams_match"] is True
    assert result["counters_reconcile"] is True
    assert result["transport_reconcile"] is True
    assert result["slo_segments"]["ttft"]["count"] > 0
    assert result["slo_segments"]["tpot"]["count"] > 0
    c = result["chaos"]
    assert c["sigkills"] >= 2
    assert c["streams_match"] is True
    assert c["counters_reconcile"] is True
    assert c["transport_reconcile"] is True


def test_fold_onchip_renders_fleet_decode_stage(tmp_path, capsys,
                                               monkeypatch):
    """ISSUE 17: tools/fold_onchip.py renders fleet-decode rows
    (aggregate tok/s, capacity ratio, TTFT/TPOT SLOs, migrations/
    replays, chaos SIGKILL evidence) and flags a gate, bit-identity,
    or reconciliation break loudly; logs without the key fold
    unchanged."""
    fold = _load_module("fold_onchip_for_fd_test",
                        "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    row = {"ok": True, "metric": "fleet_decode_tokens_per_sec",
           "fleet_decode_tokens_per_sec": 86.4,
           "speedup_vs_single_engine": 1.96, "replicas": 2,
           "ttft_p50_ms": 40.1, "ttft_p99_ms": 95.2,
           "tpot_p50_ms": 11.3, "tpot_p99_ms": 31.7,
           "migrations": 3, "replays": 1,
           "streams_match": True, "counters_reconcile": True,
           "transport_reconcile": True, "speedup_gate_1p7x": True,
           "chaos": {"availability_pct": 62.5, "sigkills": 2,
                     "replays": 2, "streams_match": True,
                     "counters_reconcile": True,
                     "transport_reconcile": True}}
    (logs / "fleet_decode.out").write_text(json.dumps(row) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "86 tok/s" in out
    assert "x1.96 vs 1 engine" in out
    assert "2 proc replicas" in out
    assert "ttft p99 95.2 ms" in out
    assert "tpot p99 31.7 ms" in out
    assert "3 migrations" in out and "1 replays" in out
    assert "chaos: 62.5% avail, 2 SIGKILLs/2 replays" in out
    assert "MISMATCH" not in out
    # a failed capacity gate is a loud MISMATCH, not a quiet number
    row["speedup_gate_1p7x"] = False
    (logs / "fleet_decode.out").write_text(json.dumps(row) + "\n")
    assert fold.main() == 0
    assert "MISMATCH" in capsys.readouterr().out


def test_tpu_watch_fleet_decode_flavor():
    """tools/tpu_watch.sh grows a `fleet-decode` flavor tailing the
    decode router's control plane (session terminals, migration/
    replay counters, per-replica KV occupancy, TTFT/TPOT p99). It
    must sit ABOVE the `fleet` flavor (whose match would swallow the
    "fleet-decode" argument), and the PR 16 `decode` flavor's glob
    must now EXCLUDE fleet_decode streams — `bench_fleet_decode
    .jsonl` matches `*decode*.jsonl` too."""
    sh = open(os.path.join(_ROOT, "tools", "tpu_watch.sh")).read()
    fdec = sh.index('"$1" = "fleet-decode"')
    flt = sh.index('"$1" = "fleet"')
    dec = sh.index('"$1" = "decode"')
    assert fdec < flt, "fleet-decode flavor must precede fleet"
    block = sh[fdec:flt]
    for key in ("*fleet_decode*.jsonl", "decode_requests",
                "decode_replies", "decode_failed",
                "decode_migrations", "decode_replays",
                "replica_decode", "ttft", "tpot"):
        assert key in block, f"fleet-decode watch block missing {key}"
    # ISSUE 19: per-replica quant bit renders only when armed
    assert 'd.get("quant")' in block
    dec_block = sh[dec:dec + 600]
    assert "grep -v fleet" in dec_block, (
        "decode flavor glob must exclude fleet_decode router streams")


def test_byte_diet_matrix_flags_validate_in_argparse():
    """An invalid --slot-dtype/--bn-stats-dtype must die in argparse,
    before any jax work can measure the wrong thing (the same
    loud-failure contract as unknown flags)."""
    for flag in ("--slot-dtype", "--bn-stats-dtype"):
        proc, _ = _run_stage(["--stage", "resnet", flag, "fp8"],
                             timeout=60)
        assert proc.returncode != 0, f"{flag}=fp8 accepted"


def test_unknown_stage_is_loud():
    # A typo'd stage must not silently fall through into the full
    # multi-stage driver flow (23-minute default deadline).
    proc, result = _run_stage(["--stage", "probee"], timeout=60)
    assert proc.returncode != 0
    assert result is not None and result["ok"] is False
    assert "unknown stage" in result["error"]


def test_eager_overhead_emits_stats_line_and_final_json():
    """benchmarks/eager_overhead.py output contract: one
    `cache_stats <name> ...` line per executable cache plus ONE final
    JSON line (the same last-JSON-line shape bench.py stages emit and
    tools/fold_onchip.py parses), carrying the
    LRU-vs-FIFO retrace demo numbers."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(_ROOT, "benchmarks", "eager_overhead.py"),
         "--cpu", "--quick"],
        capture_output=True, text=True, timeout=600, cwd=_ROOT,
        env=dict(os.environ),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    for cache in ("dag_backward", "fused_opt", "op_exec"):
        assert any(ln.startswith(f"cache_stats {cache} ")
                   for ln in lines), f"no cache_stats line for {cache}"
    # same parse the runner tooling applies: LAST JSON line wins
    last = None
    for line in lines:
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            last = json.loads(line)
    assert last is not None, "no final JSON line"
    assert last["ok"] is True
    assert last["eager_step_ms"] > 0 and last["graph_step_ms"] > 0
    demo = last["demo"]
    # the acceptance behavior: hot retraces flat under LRU after
    # warmup, growing under the legacy FIFO policy
    assert demo["lru"]["steady_hot_retraces_per_round"] == 0
    assert demo["fifo"]["steady_hot_retraces_per_round"] > 0
    # accumulation A/B (ISSUE 4): deterministic contract — one fused
    # apply per accum-n step vs n per split run; timing fields
    # present but not asserted (CI boxes are noisy)
    accum = last["accum"]
    assert accum["n"] == 8
    assert accum["apply_calls_per_step"]["accum8"] == 1.0
    assert accum["apply_calls_per_step"]["accum1"] == 8.0
    assert accum["split_steps_ms"] > 0 and accum["accum_step_ms"] > 0
    assert "dispatch_amortization_pct" in accum
    # tracer A/B (ISSUE 5): the deterministic contract — the disabled
    # tracer records literally nothing, the enabled one spans every
    # eager step; the percentage is reported but not asserted (noise)
    tr = last["trace"]
    assert tr["spans_per_step"]["disabled"] == 0
    assert tr["spans_per_step"]["enabled"] >= 1
    assert "trace_overhead_pct" in tr
    assert tr["off_step_ms"] > 0 and tr["on_step_ms"] > 0
    # proc-fleet tracer A/B (ISSUE 15): a REAL 2-worker fleet, off
    # arm records literally nothing, on arm ships worker spans into a
    # merged trace spanning >= 2 pids; the percentage is reported
    # (the < 2% acceptance is judged on quiet hardware, not CI noise)
    ft = last["fleet_trace"]
    assert ft["spans"]["disabled"] == 0
    assert ft["spans"]["enabled"] >= 1
    assert ft["pids_in_merged_trace"] >= 2
    assert "fleet_trace_overhead_pct" in ft
    assert ft["off_req_ms"] > 0 and ft["on_req_ms"] > 0
    # AOT cold-vs-warm A/B (ISSUE 6 acceptance): the process-fresh
    # warm start loads the serialized step WITHOUT tracing (hit
    # counter = 1, zero traces/retraces), bit-identical loss, and
    # time-to-first-step drops >= 3x vs the export-cache-off cold
    # run. All three fleet regimes are reported: full-cold (trace +
    # compile), trace-only (XLA cache warm — the pre-PR-6 steady
    # state), and warm; the trace-only ratio must still favor warm.
    ws = last["warm_start"]
    assert ws["export_hits"] == 1
    assert ws["export_traces"] == 0
    assert ws["dag_retraces"] == 0
    assert ws["loss_match"] is True
    assert ws["cold_first_step_s"] > 0 and ws["warm_first_step_s"] > 0
    assert ws["trace_only_first_step_s"] > 0
    assert ws["warm_start_speedup"] >= 3.0, (
        f"warm start only {ws['warm_start_speedup']}x vs cold")
    assert ws["speedup_vs_trace_only"] > 1.0, (
        "warm start must beat the trace-only (compile-cached) regime")
    # ISSUE 7 satellite: the A/B's serving arm measures time-to-first-
    # REPLY through the ACTUAL request path (ServingEngine), and a
    # warm worker's serving forward loads (hits=1) without tracing,
    # reply bit-identical to the cold process's
    assert ws["serve_export_hits"] == 1
    assert ws["serve_export_traces"] == 0
    assert ws["reply_match"] is True
    assert ws["serve_cold_first_reply_s"] > 0
    assert ws["serve_warm_first_reply_s"] > 0
    assert "serve_warm_speedup" in ws


def test_resnet_tuned_stage_loads_persisted_config(tmp_path):
    """ISSUE 9: `bench.py --stage resnet --tuned` loads the
    autotuner's persisted best-known config end-to-end on CPU — the
    tuned knobs actually arm (accum geometry in the result), and the
    result JSON carries `tuned_config` + its provenance."""
    from singa_tpu import tuning

    store = str(tmp_path / "tuned.json")
    tuning.TunedStore(store).put(
        "fp-test", "v5e",
        {"slot_dtype": "bfloat16", "grad_accum": 2},
        999.0, provenance={"source": "cost-model"}, alias="resnet")
    proc, result = _run_stage(
        ["--stage", "resnet", "--batch", "4", "--steps", "1",
         "--image-size", "24", "--tuned", "--deadline", "150"],
        timeout=300, extra_env={"SINGA_TPU_TUNED_STORE": store})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None and result["ok"] is True
    assert result["tuned_config"] == {"slot_dtype": "bfloat16",
                                      "grad_accum": 2}
    assert result["accum"] == 2 and result["slot_dtype"] == "bfloat16"
    prov = result["tuned_provenance"]
    assert prov["score"] == 999.0 and prov["source"] == "cost-model"
    # explicit CLI flags outrank the store: an empty store degrades
    # loudly to defaults (no tuned_config key), never crashes
    proc2, result2 = _run_stage(
        ["--stage", "resnet", "--batch", "4", "--steps", "1",
         "--image-size", "24", "--tuned", "--deadline", "150"],
        timeout=300,
        extra_env={"SINGA_TPU_TUNED_STORE": str(tmp_path / "no.json")})
    assert proc2.returncode == 0, proc2.stderr[-2000:]
    assert result2["ok"] is True and "tuned_config" not in result2
    # both runs emit a MEASURED-score record for their effective
    # config — the --metrics-jsonl feedback loop's source
    assert result["measured_config_jsonl"]
    assert result2["measured_config_jsonl"]


def test_fold_onchip_renders_tuned_marker(tmp_path, capsys,
                                          monkeypatch):
    """ISSUE 9: tools/fold_onchip.py marks autotuned rows `tuned=✓`;
    old logs (no `tuned_config` key) render unchanged."""
    fold = _load_module("fold_onchip_for_test", "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    (logs / "resnet_tuned.out").write_text(json.dumps(
        {"ok": True, "ips": 2100.0, "step_ms": 60.9, "batch": 128,
         "precision": "bf16",
         "tuned_config": {"slot_dtype": "bfloat16"},
         "tuned_provenance": {"score": 2500.0}}) + "\n")
    (logs / "resnet_old.out").write_text(json.dumps(
        {"ok": True, "ips": 900.0, "step_ms": 142.2, "batch": 128,
         "precision": "fp32"}) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    tuned_line = [ln for ln in out.splitlines() if "2100.0" in ln][0]
    assert "tuned=✓" in tuned_line
    old_line = [ln for ln in out.splitlines() if "900.0" in ln][0]
    assert "tuned" not in old_line


# ---------------------------------------------------------------------------
# ISSUE 10: the multi-axis parallel stage
# ---------------------------------------------------------------------------
def test_parallel_stage_contract():
    """`bench.py --stage parallel` on the (virtual) 8-device CPU
    mesh: the pipeline arm reports images/sec + measured-vs-analytic
    bubble fraction, the MoE arm tokens/sec + dropped-token fraction,
    and the result carries the shared stage breakdown + metrics
    path."""
    proc, r = _run_stage(["--stage", "parallel", "--steps", "4",
                          "--deadline", "200"], timeout=280)
    assert r is not None, proc.stderr[-2000:]
    assert r.get("ok"), r
    assert r["pipeline_images_per_sec"] > 0
    assert r["mesh_devices"] == 8
    assert r["schedule"] == "1f1b"
    assert abs(r["bubble_fraction_analytic"]
               - (r["pipe"] - 1)
               / (r["microbatches"] + r["pipe"] - 1)) < 1e-3
    # measured bubble is reported NEXT TO the analytic value (CPU
    # virtual devices share cores, so only presence is pinned)
    assert "bubble_fraction_measured" in r
    assert r["moe_tokens_per_sec"] > 0
    assert 0.0 <= r["dropped_token_fraction"] <= 1.0
    assert r["parallel_stats"]["pipeline"]["schedule"] == "1f1b"
    assert "stage_seconds" in r and "metrics_jsonl" in r


def test_parallel_row_rides_the_driver_ramp():
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert 'run_stage("parallel"' in src
    assert 'result_extra["pipeline_images_per_sec"]' in src
    assert 'result_extra["moe_tokens_per_sec"]' in src


def test_fold_onchip_renders_parallel_stage(tmp_path, capsys,
                                            monkeypatch):
    fold = _load_module("fold_onchip_for_test2",
                        "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    (logs / "parallel.out").write_text(json.dumps(
        {"ok": True, "pipeline_images_per_sec": 6492.7,
         "bubble_fraction_measured": 0.31,
         "bubble_fraction_analytic": 0.2727,
         "pipe": 4, "microbatches": 8, "schedule": "1f1b",
         "moe_tokens_per_sec": 33966.5,
         "dropped_token_fraction": 0.021, "experts": 4}) + "\n")
    # an old-format row in the same dir folds unchanged
    (logs / "resnet_old.out").write_text(json.dumps(
        {"ok": True, "ips": 100.0, "step_ms": 10.0, "batch": 32,
         "precision": "fp32"}) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "6492.7 img/s" in out
    assert "P=4 M=8 1f1b" in out
    assert "0.31" in out and "0.2727 analytic" in out
    assert "33966 tok/s" in out or "33967 tok/s" in out
    assert "dropped 0.021" in out
    assert "100.0 img/s" in out  # old log unchanged


def test_fleet_stage_contract_and_acceptance():
    """ISSUE 11: the fleet stage's JSON contract — router over N
    replicas under Poisson load, bit-identical replies, exact
    fleet-wide reconciliation; the --chaos arm fires hard replica
    kills mid-load and still reconciles with bounded availability."""
    proc, result = _run_stage(
        ["--stage", "fleet", "--requests", "200", "--replicas", "2",
         "--deadline", "180", "--chaos"], timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None, "no JSON result line on stdout"
    assert result["ok"] is True
    assert result["metric"] == "fleet_requests_per_sec"
    for k in ("fleet_requests_per_sec", "replicas", "p50_ms",
              "p99_ms", "delivered", "failed", "refused",
              "replies_match", "routed", "failovers", "restarts",
              "counters_reconcile", "speedup_vs_sequential",
              "stage_seconds", "export_cache", "metrics_jsonl",
              "latency_breakdown", "trace"):
        assert k in result, f"fleet result missing {k}"
    assert result["replicas"] == 2
    assert result["fleet_requests_per_sec"] > 0
    assert result["replies_match"] is True
    assert result["counters_reconcile"] is True
    assert result["metrics_jsonl"] == os.path.join(
        "metrics", "bench_fleet.jsonl")
    # ISSUE 15: distributed tracing rode the clean arm — per-segment
    # latency decomposition + ONE merged Chrome timeline on disk
    lb = result["latency_breakdown"]
    for seg in ("queue_wait", "dispatch", "reply"):
        assert seg in lb and lb[seg]["p99_ms"] >= 0, lb
    tb = result["trace"]
    assert tb["span_count"] > 0 and tb["trace_ids"] > 0
    tr_path = os.path.join(_ROOT, tb["chrome_trace"])
    assert os.path.exists(tr_path)
    evs = json.load(open(tr_path))["traceEvents"]
    assert any((e.get("args") or {}).get("trace") for e in evs)
    # the aggregate record reached the fleet JSONL (tpu_watch/fleet_top
    # render it)
    from singa_tpu import trace as trace_mod

    recs = trace_mod.read_metrics(os.path.join(
        _ROOT, "metrics", "bench_fleet.jsonl"))
    assert any((r.get("extra") or {}).get("event") == "aggregate"
               and (r.get("extra") or {}).get("segments")
               for r in recs)
    c = result["chaos"]
    for k in ("availability_pct", "delivered", "failed", "p50_ms",
              "p99_ms", "replies_match", "failovers", "restarts",
              "ejections", "kills", "counters_reconcile"):
        assert k in c, f"fleet chaos sub-dict missing {k}"
    assert c["kills"] >= 1, "chaos arm fired no hard replica kill"
    assert c["replies_match"] is True
    assert c["counters_reconcile"] is True
    assert 0.0 < c["availability_pct"] <= 100.0
    # ISSUE 20: the online SLO engine rode both arms.  Clean arm:
    # fleet-merged sketch p99s cross-validated against the post-hoc
    # sorted trace samples (count parity gates each segment).  Chaos
    # arm: at least one availability burn-rate alert AND one
    # per-replica anomaly alert walked the EXACT pending -> firing ->
    # resolved lifecycle, discovered from the alerts JSONL.
    s = result["slo"]
    assert s["crosscheck"], "no segments passed count-parity gating"
    assert s["crosscheck_ok"] is True, s
    sa = c["slo_alerts"]
    assert sa["records"] > 0, "chaos arm wrote no alert records"
    assert sa["full_lifecycles"] >= 1
    assert sa["availability_fired_resolved"] is True, sa
    assert sa["anomaly_fired_resolved"] is True, sa
    assert sa["anomaly_replicas"], sa
    apath = os.path.join(_ROOT, sa["alerts_jsonl"])
    assert os.path.exists(apath)


def test_fleet_row_rides_the_driver_ramp():
    """The fleet metric reaches the driver result table
    (`fleet_requests_per_sec` in result_extra), like serve/parallel."""
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert 'run_stage("fleet"' in src
    assert 'result_extra["fleet_requests_per_sec"]' in src


def test_serve_chaos_client_honors_retry_after():
    """BUGFIX (ISSUE 11): the serve-stage chaos client used to treat
    ServeOverloadError as terminal; it must route submits through the
    retry-after-aware helper so measured availability reflects the
    documented contract."""
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert "submit_with_backoff" in src
    assert src.count("submit_with_backoff") >= 2, (
        "both the serve chaos arm and the fleet stage must use the "
        "retry-after-aware client helper")


def test_fold_onchip_renders_fleet_stage(tmp_path, capsys,
                                         monkeypatch):
    """ISSUE 11: tools/fold_onchip.py renders fleet rows (req/s,
    replica count, SLO percentiles, failovers/restarts, chaos
    availability + kill evidence); old serve logs fold unchanged and
    a reconciliation break is flagged loudly."""
    fold = _load_module("fold_onchip_for_test", "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    row = {"ok": True, "metric": "fleet_requests_per_sec",
           "fleet_requests_per_sec": 5271.8, "replicas": 3,
           "p50_ms": 11.5, "p99_ms": 17.1, "failovers": 4,
           "restarts": 1, "replies_match": True,
           "counters_reconcile": True,
           "chaos": {"availability_pct": 98.0, "p99_ms": 591.4,
                     "kills": 2, "failovers": 56, "restarts": 2,
                     "replies_match": True,
                     "counters_reconcile": True}}
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    # an old serve-format row in the same dir folds unchanged
    (logs / "serve.out").write_text(json.dumps(
        {"ok": True, "serve_requests_per_sec": 8123.4,
         "p50_ms": 2.1, "p99_ms": 7.9}) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "5271.8 req/s" in out
    assert "3 replicas" in out
    assert "4 failovers" in out and "1 restarts" in out
    assert "chaos: 98.0% avail" in out
    assert "2 kills/56 failovers/2 restarts" in out
    assert "8123.4 req/s" in out  # old serve log unchanged
    assert "MISMATCH" not in out
    # a broken reconciliation flag is loud
    row["chaos"]["counters_reconcile"] = False
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    assert fold.main() == 0
    assert "MISMATCH" in capsys.readouterr().out


def test_fleet_stage_proc_transport_wiring(tmp_path, capsys,
                                           monkeypatch):
    """ISSUE 13: the fleet stage grows `--transport proc` (worker
    subprocesses, real SIGKILLs in the chaos arm, transport ledger in
    the result) and tools/fold_onchip.py renders the proc row —
    naming the transport, labeling kills as SIGKILLs, and flagging a
    broken transport ledger loudly. Engine rows and old logs render
    unchanged (pinned above)."""
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert '"--transport"' in src
    assert "transport=a.transport" in src
    assert "proc_sigkill" in src, (
        "the proc chaos arm must fire REAL SIGKILLs")
    assert "reconcile_transport" in src or "replicas=reps" in src, (
        "the proc arm must check the transport ledger")
    fold = _load_module("fold_onchip_proc_test", "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    row = {"ok": True, "metric": "fleet_requests_per_sec",
           "fleet_requests_per_sec": 48.8, "replicas": 2,
           "transport": "proc", "p50_ms": 3.0, "p99_ms": 9.9,
           "replies_match": True, "counters_reconcile": True,
           "transport_reconcile": True,
           "chaos": {"availability_pct": 98.2, "p99_ms": 1083.7,
                     "kills": 2, "failovers": 2, "restarts": 2,
                     "replies_match": True, "counters_reconcile": True,
                     "transport_reconcile": True}}
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "transport=proc" in out
    assert "2 SIGKILLs" in out
    assert "MISMATCH" not in out
    # a broken transport ledger is loud even when the serve-side
    # counters reconcile
    row["transport_reconcile"] = False
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    assert fold.main() == 0
    assert "MISMATCH" in capsys.readouterr().out


def test_fleet_stage_tcp_net_chaos_wiring(tmp_path, capsys,
                                          monkeypatch):
    """ISSUE 18: the fleet stage grows `--transport tcp` +
    `--net-faults` (listen-mode workers behind a deterministic
    ChaosProxy; net-fault evidence DISCOVERED from proxy + parent
    counters) and tools/fold_onchip.py renders the net block —
    frame-fault rate, partitions, reconnects, replay/gap counts, and
    a loud OFFSET-INSANE flag. A tcp chaos row WITHOUT the net block
    (and every older log) renders exactly as before."""
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert '"tcp"' in src and '"--net-faults"' in src
    assert "net_faults=a.net_faults" in src
    assert "net_chaos_snapshot" in src, (
        "net evidence must be discovered from the proxy counters")
    assert "net_partition" in src, (
        "the chaos schedule must pin at least one real partition")
    fold = _load_module("fold_onchip_tcp_test", "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    row = {"ok": True, "metric": "fleet_requests_per_sec",
           "fleet_requests_per_sec": 41.1, "replicas": 2,
           "transport": "tcp", "p50_ms": 3.4, "p99_ms": 11.2,
           "replies_match": True, "counters_reconcile": True,
           "transport_reconcile": True,
           "chaos": {"availability_pct": 97.5, "p99_ms": 1201.0,
                     "kills": 2, "failovers": 2, "restarts": 2,
                     "replies_match": True, "counters_reconcile": True,
                     "transport_reconcile": True,
                     "net": {"frame_fault_rate_pct": 7.3,
                             "partitions": 2, "reconnects": 3,
                             "replay_frames_detected": 1,
                             "gap_frames_detected": 1,
                             "offset_sane": True}}}
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "transport=tcp" in out
    assert "2 SIGKILLs" in out  # tcp kills are real SIGKILLs too
    assert "net: 7.3% frames faulted" in out
    assert "2 partitions" in out and "3 reconnects" in out
    assert "replay/gap 1/1" in out
    assert "MISMATCH" not in out and "OFFSET-INSANE" not in out
    # an insane clock-offset estimate is loud
    row["chaos"]["net"]["offset_sane"] = False
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    assert fold.main() == 0
    assert "OFFSET-INSANE" in capsys.readouterr().out
    # a tcp chaos row WITHOUT the net block renders the ISSUE 13 way
    del row["chaos"]["net"]
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "net:" not in out and "OFFSET-INSANE" not in out


def test_checked_in_metrics_cache_buckets_match_live_stats():
    """ISSUE 15 satellite (fixture audit): every cache bucket a
    checked-in bench JSONL record carries must exist in the LIVE
    `cache_stats()` surface — a fixture generated by an uncommitted
    module (the `decode`/`generate` buckets bench_decode.jsonl once
    carried) is unverifiable evidence and must not ride along."""
    # importing these registers every committed cache
    from singa_tpu import (autograd, export_cache, fleet, opt,  # noqa
                           resilience, serve, stats, trace,
                           tuning)  # noqa: F401

    live = set(stats.cache_stats().keys())
    assert live, "cache_stats() returned nothing"
    import glob

    fixtures = sorted(glob.glob(os.path.join(_ROOT, "metrics",
                                             "bench_*.jsonl")))
    checked = 0
    for path in fixtures:
        for rec in trace.read_metrics(path):
            cache = rec.get("cache")
            if not isinstance(cache, dict):
                continue
            checked += 1
            unknown = set(cache) - live
            assert not unknown, (
                f"{os.path.basename(path)} carries cache bucket(s) "
                f"{sorted(unknown)} no committed module registers — "
                "regenerate or remove the fixture")
    assert checked > 0, "no bench fixture records found to audit"


def test_fleet_stage_result_carries_trace_blocks():
    """ISSUE 15: the fleet stage's `latency_breakdown` and `trace`
    result blocks are produced by trace.aggregate_fleet /
    FleetRouter.export_trace — pinned at the source level (the full
    stage contract test above exercises them end to end)."""
    src = open(os.path.join(_ROOT, "bench.py")).read()
    assert "aggregate_fleet" in src
    assert "export_trace" in src
    assert '"latency_breakdown": latency_breakdown' in src
    assert '"trace": trace_block' in src
    assert "set_tracing(True" in src and "set_tracing(False)" in src


def test_tpu_watch_fleet_segments_only_when_present():
    """ISSUE 15 satellite: tools/tpu_watch.sh fleet renders the
    per-segment latency columns ONLY for records that carry them —
    old fleet logs print exactly as before (conditional access,
    no new unconditional columns)."""
    src = open(os.path.join(_ROOT, "tools", "tpu_watch.sh")).read()
    assert 'x.get("segments")' in src
    for seg in ("queue_wait", "ipc", "dispatch", "reply"):
        assert f'"{seg}"' in src
    assert 'x.get("availability_pct")' in src
    # worker data-plane streams must not shadow the router's log
    assert "worker" in src.split('if [ "$1" = "fleet" ]')[1].split(
        "exit $?")[0]


def test_fold_onchip_renders_fleet_trace_blocks(tmp_path, capsys,
                                                monkeypatch):
    """ISSUE 15: fold_onchip renders the fleet row's per-segment p99
    decomposition + merged-trace evidence; rows WITHOUT the new
    blocks (old logs) render byte-identically to the ISSUE 11/13
    pins above."""
    fold = _load_module("fold_onchip_trace_test",
                        "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    base = {"ok": True, "metric": "fleet_requests_per_sec",
            "fleet_requests_per_sec": 48.8, "replicas": 2,
            "transport": "proc", "p50_ms": 3.0, "p99_ms": 9.9,
            "replies_match": True, "counters_reconcile": True,
            "transport_reconcile": True}
    row = dict(base)
    row["latency_breakdown"] = {
        "queue_wait": {"count": 10, "p50_ms": 0.4, "p99_ms": 1.2},
        "ipc": {"count": 10, "p50_ms": 0.2, "p99_ms": 0.7},
        "dispatch": {"count": 10, "p50_ms": 1.1, "p99_ms": 2.3},
        "reply": {"count": 10, "p50_ms": 0.1, "p99_ms": 0.3}}
    row["trace"] = {"chrome_trace": "metrics/bench_fleet_trace.json",
                    "span_count": 321, "trace_ids": 40, "pids": 3,
                    "spans_dropped": 0}
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "p99 segs q1.2/i0.7/d2.3/r0.3 ms" in out
    assert "trace: 321 spans/3 pids" in out
    # an old row (no blocks) renders with no seg/trace column at all
    (logs / "fleet.out").write_text(json.dumps(base) + "\n")
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "segs" not in out and "spans" not in out


def test_committed_bench_fixtures_stay_one_run():
    """ISSUE 19 fixture diet: the COMMITTED bench metrics fixtures
    hold exactly one canonical run each — one writer pid, bounded
    line count. Tier-1 runs append fresh runs to the working files
    (the contract tests above do exactly that), so this guard reads
    the INDEX blob (`git show :path` — falls back to HEAD when the
    path isn't staged): committing a re-bloated multi-run fixture
    fails here, a dirty unstaged working copy does not. Seed sizes
    were 442/723/561 lines of stacked runs; one run is well under
    250."""
    fixtures = [
        "metrics/bench_serve_decode.jsonl",
        "metrics/bench_fleet_decode_w0.worker.jsonl",
        "metrics/bench_fleet_decode_w1.worker.jsonl",
    ]
    for rel in fixtures:
        proc = subprocess.run(
            ["git", "show", f":{rel}"],
            capture_output=True, text=True, cwd=_ROOT)
        if proc.returncode != 0:
            proc = subprocess.run(
                ["git", "show", f"HEAD:{rel}"],
                capture_output=True, text=True, cwd=_ROOT)
        if proc.returncode != 0:
            pytest.skip("not a git checkout — nothing committed "
                        "to guard")
        lines = proc.stdout.splitlines()
        assert lines, f"{rel}: committed fixture is empty"
        assert len(lines) <= 250, (
            f"{rel}: {len(lines)} committed lines — fixture has "
            f"re-bloated past one canonical run; prune to the last "
            f"pid's records before committing")
        pids = {json.loads(ln).get("pid") for ln in lines}
        assert len(pids) == 1, (
            f"{rel}: {len(pids)} writer pids in the committed "
            f"fixture — multiple stacked runs; keep one")


# ---------------------------------------------------------------------------
# ISSUE 20: SLO tooling satellites — metrics_lint, fold/health/top renders
# ---------------------------------------------------------------------------
def test_metrics_lint_committed_fixtures_clean(tmp_path):
    """tools/metrics_lint.py validates every COMMITTED telemetry
    fixture against the schema-version registry (the same INDEX-blob
    read as the fixture-diet guard: a dirty working copy must not
    flake the lint)."""
    lint = _load_module("metrics_lint_for_test",
                        "tools/metrics_lint.py")
    import subprocess
    paths = []
    for rel in ("metrics/bench_serve_decode.jsonl",
                "metrics/bench_fleet_decode_w0.worker.jsonl",
                "metrics/bench_fleet_decode_w1.worker.jsonl"):
        proc = subprocess.run(["git", "show", f":{rel}"],
                              capture_output=True, text=True,
                              cwd=_ROOT)
        if proc.returncode != 0:
            proc = subprocess.run(["git", "show", f"HEAD:{rel}"],
                                  capture_output=True, text=True,
                                  cwd=_ROOT)
        if proc.returncode != 0:
            pytest.skip("not a git checkout")
        p = tmp_path / os.path.basename(rel)
        p.write_text(proc.stdout)
        paths.append(str(p))
    assert lint.main(paths) == 0, "committed fixtures must lint clean"


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


def test_fold_onchip_renders_slo_columns(tmp_path, capsys,
                                         monkeypatch):
    """ISSUE 20: fold_onchip renders the fleet row's SLO evidence —
    crosscheck segment count (MISMATCH when the sketch p99 drifted
    from post-hoc), and the chaos arm's alert-lifecycle counts
    (MISMATCH when a required alert class never fired+resolved). A
    pre-20 row without the slo block renders byte-identically."""
    fold = _load_module("fold_onchip_slo_test",
                        "tools/fold_onchip.py")
    logs = tmp_path / "onchip_logs"
    logs.mkdir()
    old_row = {"ok": True, "metric": "fleet_requests_per_sec",
               "fleet_requests_per_sec": 5271.8, "replicas": 3,
               "p50_ms": 11.5, "p99_ms": 17.1, "failovers": 0,
               "restarts": 0, "replies_match": True,
               "counters_reconcile": True}
    (logs / "fleet.out").write_text(json.dumps(old_row) + "\n")
    monkeypatch.setattr(fold, "LOGS", str(logs))
    assert fold.main() == 0
    base_out = capsys.readouterr().out
    assert "slo xcheck" not in base_out and "MISMATCH" not in base_out

    row = dict(old_row,
               slo={"rel_err": 0.02,
                    "crosscheck": {"reply": {"ok": True},
                                   "ipc": {"ok": True}},
                    "crosscheck_ok": True},
               chaos={"availability_pct": 98.0, "p99_ms": 591.4,
                      "kills": 2, "failovers": 5, "restarts": 2,
                      "replies_match": True,
                      "counters_reconcile": True,
                      "slo_alerts": {"records": 12,
                                     "full_lifecycles": 4,
                                     "availability_fired_resolved":
                                         True,
                                     "anomaly_fired_resolved": True}})
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    assert fold.main() == 0
    out = capsys.readouterr().out
    assert "slo xcheck 2 segs" in out
    assert "alerts 12 rec/4 full" in out
    assert "MISMATCH" not in out
    # a drifted sketch OR a missing alert class is loud
    row["slo"]["crosscheck_ok"] = False
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    assert fold.main() == 0
    assert "MISMATCH" in capsys.readouterr().out
    row["slo"]["crosscheck_ok"] = True
    row["chaos"]["slo_alerts"]["anomaly_fired_resolved"] = False
    (logs / "fleet.out").write_text(json.dumps(row) + "\n")
    assert fold.main() == 0
    assert "MISMATCH" in capsys.readouterr().out


def test_serve_health_folds_alert_severity(tmp_path):
    """ISSUE 20: a health snapshot carrying the SLO alert-counts
    block renders `alerts[...]` and the WORST firing severity folds
    into the exit code (page => 2/unhealthy, ticket => 1/degraded);
    a snapshot WITHOUT the block renders byte-identically to pre-20
    (append-only probe contract, same discipline as decode[...])."""
    import importlib.util

    spec_ = importlib.util.spec_from_file_location(
        "serve_health_for_slo_test",
        os.path.join(_ROOT, "tools", "serve_health.py"))
    sh = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(sh)
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
    """ISSUE 20: fleet_top grows an alert panel (state replayed from
    the alerts JSONL, active alerts listed firing-first) and a
    --follow mode; --iterations 1 bounds a follow pass for CI."""
    ft = _load_module("fleet_top_slo_test", "tools/fleet_top.py")
    with open(tmp_path / "bench_fleet.jsonl", "w") as f:
        f.write(json.dumps({"time": 1.0, "step": 1, "extra": {
            "event": "route", "fleet_requests": 4,
            "fleet_replies": 4, "routed": 4}}) + "\n")
    rec = {"schema": 1, "kind": "slo_alert", "time": 1.0, "mono": 0.5,
           "alert": "availability", "rule": "fast",
           "severity": "page", "replica": "-", "state": "pending",
           "episode": 1, "burn_long": 99.0, "burn_short": 99.0,
           "value": 99.0, "threshold": 14.4}
    with open(tmp_path / "bench_fleet_alerts.jsonl", "w") as f:
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


def test_tpu_watch_slo_flavor():
    """ISSUE 20: tools/tpu_watch.sh grows an `slo` flavor that tails
    the newest alerts JSONL and renders state transitions."""
    src = open(os.path.join(_ROOT, "tools", "tpu_watch.sh")).read()
    slo_i = src.index('"$1" = "slo"')
    tune_i = src.index('"$1" = "tune"')
    assert slo_i < tune_i
    block = src[slo_i:tune_i]
    for key in ("*alerts*.jsonl", "slo_alert", "pending", "firing",
                "resolved", "episode"):
        assert key in block, f"slo watch block missing {key}"
