"""Summarize on-chip stage logs into a BASELINE-ready table.

Reads `onchip_logs/<stage>.out` — one file per `bench.py --stage`,
its output appended attempt after attempt — takes
each file's LAST result-JSON line and prints one row per stage, ready
to fold into BASELINE.md. A result with trailing non-JSON output
after it (a later attempt that died before printing its result) is
flagged stale rather than reported as current.

    python tools/fold_onchip.py            # table of everything seen
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
LOGS = os.path.join(HERE, "..", "onchip_logs")


def json_lines(path):
    """Yield (parsed, line_no) for every JSON-object line."""
    with open(path, errors="replace") as f:
        for i, line in enumerate(f):
            line = line.strip()
            if line.startswith("{") and line.endswith("}"):
                try:
                    yield json.loads(line), i
                except ValueError:
                    pass


# A later attempt's startup is recognizable: bench.py's stderr logger
# stamps every line "[bench HH:MM:SS]" from stage start onward, and an
# attempt that dies before the logger even starts (import error, early
# kill) leaves a Python traceback. Plain trailing chatter (PJRT/absl
# teardown after a SUCCESSFUL result — the logs merge stdout+stderr)
# matches neither.
_ATTEMPT_MARKERS = ("[bench ", "Traceback (most recent call last")


def last_json(path):
    """(last result, stale?) — stale only when the trailing lines
    after the last result contain an attempt-start/stage-banner
    marker (a later attempt wrote output but never reached its
    result). Post-result teardown noise from the same successful
    attempt must not flag a good result [STALE]."""
    out, at = None, -1
    for obj, i in json_lines(path):
        out, at = obj, i
    if out is None:
        return None, False
    with open(path, errors="replace") as f:
        trailing = [ln for ln in list(f)[at + 1:] if ln.strip()]
    stale = any(m in ln for ln in trailing for m in _ATTEMPT_MARKERS)
    return out, stale


def _stage_breakdown(r):
    """Render the `stage_seconds` wall-time breakdown (ISSUE 5:
    setup / compile / steady; ISSUE 6 splits compile into
    trace/compile/load and adds the artifact-cache `warm=` hit-rate
    column) when a stage reports it; empty string for
    pre-observability logs so they fold unchanged."""
    ss = r.get("stage_seconds")
    if not isinstance(ss, dict):
        return ""
    out = f", t=setup {ss.get('setup')}s"
    split = "trace" in ss or "load" in ss
    if split:
        out += f"/trace {ss.get('trace')}s"
    out += f"/compile {ss.get('compile')}s"
    if split:
        out += f"/load {ss.get('load')}s"
    out += f"/steady {ss.get('steady')}s"
    ec = r.get("export_cache")
    if isinstance(ec, dict) and "hit_rate" in ec:
        out += f", warm={int(round(ec['hit_rate'] * 100))}%"
    return out


def main():
    if not os.path.isdir(LOGS):
        print("no onchip_logs/ — nothing to fold")
        return 1
    entries = []  # (stage, result-dict or None, stale)
    for name in sorted(os.listdir(LOGS)):
        path = os.path.join(LOGS, name)
        if name.endswith(".out"):  # per-stage file
            r, stale = last_json(path)
            entries.append((name[:-4], r, stale))
        elif name.endswith(".log"):  # aggregated runbook log: all lines
            for obj, _ in json_lines(path):
                entries.append((name[:-4], obj, False))
    rows = []
    for stage, r, stale in entries:
        mark = "  [STALE: a later attempt left no result]" if stale else ""
        if r is None:
            if stage.startswith("pallas_") and os.path.getsize(
                    os.path.join(LOGS, stage + ".out")) > 0:
                # these stages print a table, not a JSON contract
                rows.append((stage, "ran — see benchmarks/"
                                    "PALLAS_BENCH.md / the .out log"))
            else:
                rows.append((stage, "no result line"))
            continue
        if not r.get("ok", False) and "value" not in r:
            rows.append((stage, f"FAILED: {r.get('error', r)}" + mark))
            continue
        if "metric" in r and "value" in r:
            # driver-level result table (bench.py _final_json)
            rows.append((stage,
                         f"{r['value']} {r.get('unit', '')}".strip()
                         + f"  ({r['metric']})" + mark))
        elif "ips" in r:
            # byte-diet matrix columns render only when non-default,
            # so pre-matrix logs fold unchanged
            diet = "".join(
                f", {k}={r[k]}" for k in ("slot_dtype", "bn_stats_dtype",
                                          "xla_profile")
                if r.get(k) not in (None, "fp32", "default"))
            # accumulation matrix column (ISSUE 4): bs is the
            # EFFECTIVE batch; show the scan geometry alongside
            if r.get("accum", 1) != 1:
                diet += f", accum=x{r['accum']}(mb{r['microbatch']})"
            # autotuned row (ISSUE 9): the config came from the tuned
            # store, not hand-queued flags; old logs (no key) render
            # unchanged
            if r.get("tuned_config") is not None:
                diet += ", tuned=✓"
            diet += _stage_breakdown(r)
            rows.append((stage,
                         f"{r['ips']:.1f} img/s  ({r['step_ms']:.1f} "
                         f"ms/step, bs{r['batch']}, {r.get('precision')}"
                         f"{', remat' if r.get('remat') else ''}"
                         f"{diet})" + mark))
        elif "fleet_requests_per_sec" in r:
            # fleet serving (ISSUE 11): router throughput over N
            # replicas + SLO percentiles + failover/restart evidence;
            # the --chaos arm adds availability under replica kills.
            # Loud MISMATCH on a bit-identity or reconciliation break.
            bad = ("" if r.get("replies_match", True)
                   and r.get("counters_reconcile", True)
                   and r.get("transport_reconcile", True)
                   else " MISMATCH")
            fo = (f", {r['failovers']} failovers"
                  if r.get("failovers") else "")
            rst = (f", {r['restarts']} restarts"
                   if r.get("restarts") else "")
            # proc transport (ISSUE 13): name it in the row — the
            # same req/s means something different across a process
            # boundary; engine rows (and old logs) render unchanged
            tp = (f", transport={r['transport']}"
                  if r.get("transport", "engine") != "engine" else "")
            ch = ""
            if isinstance(r.get("chaos"), dict):
                c = r["chaos"]
                cbad = ("" if c.get("replies_match", True)
                        and c.get("counters_reconcile", True)
                        and c.get("transport_reconcile", True)
                        else " MISMATCH")
                kills = (f"{c.get('kills', 0)} SIGKILLs"
                         if r.get("transport") in ("proc", "tcp")
                         else f"{c.get('kills', 0)} kills")
                ch = (f", chaos: {c.get('availability_pct')}% avail, "
                      f"p99 {c.get('p99_ms')} ms, "
                      f"{kills}/"
                      f"{c.get('failovers', 0)} failovers/"
                      f"{c.get('restarts', 0)} restarts{cbad}")
                # net-fault evidence (ISSUE 18): rendered ONLY when
                # the record carries the tcp chaos block — every
                # older log folds byte-identically
                net = c.get("net")
                if isinstance(net, dict):
                    nbad = ("" if net.get("offset_sane", True)
                            in (True, None) else " OFFSET-INSANE")
                    ch += (f", net: {net.get('frame_fault_rate_pct')}%"
                           f" frames faulted, "
                           f"{net.get('partitions', 0)} partitions, "
                           f"{net.get('reconnects', 0)} reconnects, "
                           f"replay/gap "
                           f"{net.get('replay_frames_detected', 0)}/"
                           f"{net.get('gap_frames_detected', 0)}"
                           f"{nbad}")
            # distributed tracing (ISSUE 15): the per-segment latency
            # decomposition + merged-timeline evidence — rendered only
            # when the result carries the new blocks (old logs fold
            # byte-identically)
            seg = ""
            lb = r.get("latency_breakdown")
            if isinstance(lb, dict) and lb:
                parts = [f"{k[0] if k != 'queue_wait' else 'q'}"
                         f"{lb[k]['p99_ms']}"
                         for k in ("queue_wait", "ipc", "dispatch",
                                   "reply") if k in lb]
                seg = ", p99 segs " + "/".join(parts) + " ms"
            tr_ = r.get("trace")
            if isinstance(tr_, dict):
                seg += (f", trace: {tr_.get('span_count')} spans/"
                        f"{tr_.get('pids')} pids")
            # online SLO engine (ISSUE 20): the sketch-vs-post-hoc
            # crosscheck and the chaos arm's alert-lifecycle evidence
            # fold into the SAME loud MISMATCH — an online quantile
            # that drifts from the trace, or a chaos arm whose alerts
            # never fired-and-resolved, is a broken observability
            # claim, not a footnote.  Old logs (no "slo" key) fold
            # byte-identically.
            slo_r = r.get("slo")
            if isinstance(slo_r, dict):
                seg += (f", slo xcheck "
                        f"{len(slo_r.get('crosscheck', {}))} segs")
                if not slo_r.get("crosscheck_ok", True):
                    seg += " MISMATCH"
            if isinstance(r.get("chaos"), dict):
                sa = r["chaos"].get("slo_alerts")
                if isinstance(sa, dict):
                    ch += (f", alerts {sa.get('records', 0)} rec/"
                           f"{sa.get('full_lifecycles', 0)} full")
                    if not (sa.get("availability_fired_resolved",
                                   True)
                            and sa.get("anomaly_fired_resolved",
                                       True)):
                        ch += " MISMATCH"
            rows.append((stage,
                         f"{r['fleet_requests_per_sec']:.1f} req/s  "
                         f"({r.get('replicas')} replicas{tp}, p50 "
                         f"{r.get('p50_ms')} ms/p99 {r.get('p99_ms')} "
                         f"ms{fo}{rst}{bad}{seg}{ch}"
                         + _stage_breakdown(r) + ")" + mark))
        elif "fleet_decode_tokens_per_sec" in r:
            # fleet-wide KV-cached decode (ISSUE 17): aggregate
            # delivered tokens/s over N worker processes vs the
            # 1-replica engine baseline under the same burst schedule,
            # with the >=1.7x capacity gate and SIGKILL-proof chaos
            # evidence. Loud MISMATCH on a bit-identity, gate, or
            # reconciliation break. Old logs (no key) fold unchanged.
            bad = ("" if r.get("streams_match", True)
                   and r.get("counters_reconcile", True)
                   and r.get("transport_reconcile", True)
                   and r.get("speedup_gate_1p7x", True)
                   else " MISMATCH")
            mig = (f", {r['migrations']} migrations"
                   if r.get("migrations") else "")
            rp = (f", {r['replays']} replays"
                  if r.get("replays") else "")
            # quant column (ISSUE 19): rendered only when the record
            # carries an armed mode — old logs fold byte-identically
            quant = (f", quant={r['quant']}"
                     if r.get("quant", "off") != "off" else "")
            ch = ""
            if isinstance(r.get("chaos"), dict):
                c = r["chaos"]
                cbad = ("" if c.get("streams_match", True)
                        and c.get("counters_reconcile", True)
                        and c.get("transport_reconcile", True)
                        else " MISMATCH")
                ch = (f", chaos: {c.get('availability_pct')}% avail, "
                      f"{c.get('sigkills', 0)} SIGKILLs/"
                      f"{c.get('replays', 0)} replays{cbad}")
            # online SLO crosscheck (ISSUE 20) over ttft/tpot: folds
            # into MISMATCH when the fleet-merged sketch drifts from
            # the post-hoc trace percentile; old logs fold unchanged
            slo_r = r.get("slo")
            slo_col = ""
            if isinstance(slo_r, dict):
                slo_col = (f", slo xcheck "
                           f"{len(slo_r.get('crosscheck', {}))} segs")
                if not slo_r.get("crosscheck_ok", True):
                    slo_col += " MISMATCH"
            rows.append((stage,
                         f"{r['fleet_decode_tokens_per_sec']:.0f} "
                         f"tok/s  "
                         f"(x{r.get('speedup_vs_single_engine')} vs "
                         f"1 engine, {r.get('replicas')} "
                         f"{r.get('transport', 'proc')} "
                         f"replicas, ttft p99 {r.get('ttft_p99_ms')} "
                         f"ms, tpot p99 {r.get('tpot_p99_ms')} ms"
                         f"{mig}{rp}{quant}{slo_col}{bad}{ch}"
                         + _stage_breakdown(r) + ")" + mark))
        elif "serve_requests_per_sec" in r:
            # serving tier (ISSUE 7): throughput + SLO percentiles +
            # coalescing evidence, with the shared stage breakdown
            sx = (f", x{r['speedup_vs_sequential']} vs seq"
                  if "speedup_vs_sequential" in r else "")
            occ = (f", occ {r['occupancy_mean']}"
                   if "occupancy_mean" in r else "")
            # --chaos arm (ISSUE 8): availability + p99 under injected
            # faults next to the clean row; pre-chaos logs fold
            # unchanged (no "chaos" key, no column)
            ch = ""
            if isinstance(r.get("chaos"), dict):
                c = r["chaos"]
                bad = ("" if c.get("replies_match", True)
                       and c.get("counters_reconcile", True)
                       else " MISMATCH")
                ch = (f", chaos: {c.get('availability_pct')}% avail, "
                      f"p99 {c.get('p99_ms')} ms, "
                      f"{c.get('retries', 0)} retries{bad}")
            rows.append((stage,
                         f"{r['serve_requests_per_sec']:.1f} req/s  "
                         f"(p50 {r.get('p50_ms')} ms/p99 "
                         f"{r.get('p99_ms')} ms{occ}{sx}{ch}"
                         + _stage_breakdown(r) + ")" + mark))
        elif "serve_decode_tokens_per_sec" in r:
            # continuous-batching decode tier (ISSUE 16): token-
            # granularity serving throughput vs sequential generate()
            # + TTFT/TPOT SLOs; loud MISMATCH on a bit-identity or
            # reconciliation break. Old logs (no key) fold unchanged.
            # int8 arm (ISSUE 19): the quant column, the
            # bytes_accessed delta, and the migration-bytes probe
            # render only when the record carries them — every pre-19
            # (and --quant off) log folds byte-identically. The
            # PARITY gates fold into the SAME loud MISMATCH: a
            # quantized run whose streams or migrated continuations
            # diverged must not fold quietly. The byte ratio is
            # REPORTED, not gated, here: it is geometry-dependent
            # (weight-bound steps pay the dequant materialization on
            # backends without native int8 GEMM) and the strict
            # lower-bytes gate lives in tier-1 at the KV-bound
            # serving geometry.
            qb = r.get("decode_step_bytes")
            mg = r.get("migration")
            bad = ("" if r.get("streams_match", True)
                   and r.get("counters_reconcile", True)
                   and r.get("tokens_exact", True)
                   and (not isinstance(mg, dict)
                        or mg.get("resumed_match", True))
                   else " MISMATCH")
            quant = (f", quant={r['quant']}"
                     if r.get("quant", "off") != "off" else "")
            if isinstance(qb, dict) and qb.get("ratio") is not None:
                quant += f", bytes {qb['ratio']}x fp32"
            if isinstance(mg, dict) and mg.get("sessions"):
                per = mg["bytes_total"] // max(mg["sessions"], 1)
                quant += f", mig {per} B/sess"
            occ = (f", occ {r['occupancy_mean']}"
                   if "occupancy_mean" in r else "")
            ch = ""
            if isinstance(r.get("chaos"), dict):
                c = r["chaos"]
                cbad = ("" if c.get("streams_match", True)
                        and c.get("counters_reconcile", True)
                        else " MISMATCH")
                ch = (f", chaos: {c.get('availability_pct')}% avail, "
                      f"{c.get('failed', 0)} failed{cbad}")
            rows.append((stage,
                         f"{r['serve_decode_tokens_per_sec']:.0f} "
                         f"tok/s  "
                         f"(x{r.get('speedup_vs_sequential')} vs seq, "
                         f"ttft p50 {r.get('ttft_p50_ms')} ms/p99 "
                         f"{r.get('ttft_p99_ms')} ms, tpot p99 "
                         f"{r.get('tpot_p99_ms')} ms{occ}{quant}{bad}"
                         f"{ch}"
                         + _stage_breakdown(r) + ")" + mark))
        elif "pipeline_images_per_sec" in r:
            # multi-axis parallel stage (ISSUE 10): pipeline img/s +
            # measured-vs-analytic bubble, MoE tok/s + dropped
            # fraction; old logs (no key) fold unchanged
            bm = r.get("bubble_fraction_measured")
            ba = r.get("bubble_fraction_analytic")
            tuned = ", tuned=✓" if r.get("tuned_config") is not None \
                else ""
            rows.append((stage,
                         f"{r['pipeline_images_per_sec']:.1f} img/s "
                         f"(P={r.get('pipe')} M={r.get('microbatches')}"
                         f" {r.get('schedule')}, bubble "
                         f"{bm if bm is not None else '-'}"
                         f" vs {ba} analytic); moe "
                         f"{r.get('moe_tokens_per_sec', 0):.0f} tok/s "
                         f"(E={r.get('experts')}, dropped "
                         f"{r.get('dropped_token_fraction')})"
                         + tuned + _stage_breakdown(r) + mark))
        elif "tokens_per_sec" in r:
            diet = ("" if r.get("slot_dtype") in (None, "fp32")
                    else f", slot_dtype={r['slot_dtype']}")
            diet += _stage_breakdown(r)
            rows.append((stage, f"{r['tokens_per_sec']:.0f} tok/s  "
                                f"({r.get('config')}{diet})" + mark))
        elif "diffs" in r:
            d = r["diffs"].get("cpu_graph_vs_tpu_graph")
            rows.append((stage, "parity max rel "
                         + (f"{d:.4f}" if d is not None
                            else "NO TPU COLUMN") + mark))
        else:
            rows.append((stage, json.dumps(r)[:100] + pt + mark))
    width = max((len(s) for s, _ in rows), default=8)
    for stage, desc in rows:
        print(f"  {stage:<{width}}  {desc}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
