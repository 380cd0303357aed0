#!/usr/bin/env python3
"""fleet_top — one-screen fleet SLO surface (ISSUE 15).

Rolls the fleet's telemetry — the router's control-plane metrics
JSONL, the per-replica/worker serving JSONLs, and (optionally) the
merged Chrome trace `FleetRouter.export_trace` writes — into ONE
aggregated view via
`singa_tpu.trace.aggregate_fleet`:

  - availability (router replies / requests) + terminal counters
  - per-segment latency decomposition p50/p99: queue_wait / ipc /
    dispatch / reply / route — where a fleet request's time goes
  - the failover / ejection / restart / kill event timeline
  - per-worker dispatch totals (keyed by writer pid, the v2
    MetricsLogger field)
  - decode tier (ISSUE 17), when the streams carry it: session
    terminals + migration/replay counts, TTFT/TPOT p50/p99 segments,
    and per-replica KV-slot occupancy (absent fields render as
    before)

An alert panel (ISSUE 20) rides along when SLO alert streams are
present: every `*alerts*.jsonl` under --dir (or --alerts paths) is
replayed — last state per (alert, rule, replica) wins — and the
currently pending/firing alerts render as a table with burn rates.

Usage:
  tools/fleet_top.py [--dir metrics] [--trace metrics/bench_fleet_trace.json]
                     [--files a.jsonl b.jsonl ...] [--events N] [--json]
                     [--follow] [--interval S] [--iterations N]

With --dir (default ./metrics) every `*fleet*.jsonl` under it joins
the roll-up; --files names streams explicitly; --json emits the raw
schema-stable aggregate record instead of the table.  --follow
re-renders every --interval seconds (--iterations bounds the loop;
0 = until interrupted), re-reading every stream each pass so a live
fleet's tail shows up.

Exit codes: 0 = aggregated, 1 = no input records found.
"""
import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..")))


def _fmt(v, suffix=""):
    return "-" if v is None else f"{v}{suffix}"


def load_alerts(paths):
    """Parse SLO alert JSONL streams; a partial trailing line (writer
    mid-append) is skipped, not fatal."""
    recs = []
    for p in paths:
        try:
            with open(p, "r", encoding="utf-8") as f:
                for ln in f:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        rec = json.loads(ln)
                    except ValueError:
                        continue
                    if rec.get("kind") == "slo_alert":
                        recs.append(rec)
        except OSError:
            continue
    recs.sort(key=lambda r: r.get("time", 0.0))
    return recs


def alert_panel(recs):
    """Replay transitions; render the CURRENT alert surface (last
    state per (alert, rule, replica) wins — the stream is an event
    log, not a state table)."""
    cur = {}
    for r in recs:
        cur[(r.get("alert"), r.get("rule"), r.get("replica"))] = r
    active = sorted(
        (r for r in cur.values()
         if r.get("state") in ("pending", "firing")),
        key=lambda r: (r["state"] != "firing",
                       r.get("severity") != "page",
                       r.get("alert") or ""))
    firing = sum(1 for r in active if r["state"] == "firing")
    lines = [f"alerts: firing {firing}  pending "
             f"{len(active) - firing}  transitions {len(recs)}"]
    if active:
        lines.append(f"  {'alert':<24} {'rule':<6} {'replica':<14} "
                     f"{'state':<8} {'sev':<7} {'burn_s':>8} "
                     f"{'burn_l':>8}")
        for r in active:
            lines.append(
                f"  {str(r.get('alert')):<24} "
                f"{str(r.get('rule')):<6} "
                f"{str(r.get('replica')):<14} {r['state']:<8} "
                f"{str(r.get('severity')):<7} "
                f"{r.get('burn_short', 0.0):>8.3f} "
                f"{r.get('burn_long', 0.0):>8.3f}")
    return lines


def render(agg, events_n):
    lines = []
    lines.append(
        f"fleet: requests {_fmt(agg['requests'])}  replies "
        f"{_fmt(agg['replies'])}  failed {_fmt(agg['failed'])}  "
        f"rejected {_fmt(agg['rejected'])}  availability "
        f"{_fmt(agg['availability_pct'], '%')}")
    lines.append(
        f"routing: routed {_fmt(agg['routed'])}  failovers "
        f"{_fmt(agg['failovers'])}  refused {_fmt(agg['refused'])}  "
        f"ejections {_fmt(agg['ejections'])}  restarts "
        f"{_fmt(agg['restarts'])}  kills {_fmt(agg['kills'])}")
    dec = agg.get("decode") or {}
    if dec.get("requests") is not None:
        lines.append(
            f"decode: sessions {_fmt(dec['requests'])}  replies "
            f"{_fmt(dec['replies'])}  failed {_fmt(dec['failed'])}  "
            f"migrations {_fmt(dec['migrations'])}  replays "
            f"{_fmt(dec['replays'])}")
    segs = agg.get("segments") or {}
    if segs:
        lines.append(f"  {'segment':<16} {'count':>7} {'p50_ms':>9} "
                     f"{'p99_ms':>9}")
        for name in ("queue_wait", "ipc", "dispatch", "reply",
                     "route", "failover", "submit", "batch_assemble",
                     "ttft", "tpot"):
            s = segs.get(name)
            if s is None:
                continue
            lines.append(f"  {name:<16} {s['count']:>7d} "
                         f"{s['p50_ms']:>9.3f} {s['p99_ms']:>9.3f}")
    else:
        lines.append("  (no spans — pass --trace, or run with "
                     "device.set_tracing(True))")
    rd = agg.get("replica_decode") or {}
    if rd:
        lines.append(f"  {'replica':<16} {'sessions':>8} "
                     f"{'free_slots':>10} {'tok/s':>9}")
        for name in sorted(rd):
            d = rd[name]
            lines.append(
                f"  {name:<16} {d.get('active_sessions', 0):>8d} "
                f"{d.get('free_slots', 0):>10d} "
                f"{d.get('tokens_per_s', 0.0):>9.1f}")
    workers = agg.get("workers") or {}
    if workers:
        lines.append(f"  {'worker':<24} {'dispatches':>10} "
                     f"{'rows':>8} {'expired':>8} {'shed':>6} "
                     f"{'failed':>7}")
        for key in sorted(workers):
            w = workers[key]
            lines.append(f"  {key:<24} {w['dispatches']:>10d} "
                         f"{w['rows']:>8d} {w['expired']:>8d} "
                         f"{w['shed']:>6d} {w['failed']:>7d}")
    evs = agg.get("events") or []
    if evs:
        lines.append(f"events (last {min(events_n, len(evs))} of "
                     f"{len(evs)}):")
        for e in evs[-events_n:]:
            lines.append(f"  t={e.get('t')}  {e.get('replica')} -> "
                         f"{e.get('to_state')}"
                         + (f"  ({e['reason']})" if e.get("reason")
                            else ""))
    if agg.get("trace_ids"):
        lines.append(f"traces: {agg['trace_ids']} trace ids over "
                     f"{agg['span_count']} spans")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default="metrics",
                    help="directory whose *fleet*.jsonl streams join "
                         "the roll-up (default: ./metrics)")
    ap.add_argument("--files", nargs="*", default=None,
                    help="explicit metrics JSONL paths (overrides "
                         "--dir globbing)")
    ap.add_argument("--trace", default=None,
                    help="merged Chrome trace JSON "
                         "(FleetRouter.export_trace output) for the "
                         "per-segment latency decomposition")
    ap.add_argument("--events", type=int, default=8,
                    help="how many tail events to show")
    ap.add_argument("--json", action="store_true",
                    help="emit the raw aggregate record")
    ap.add_argument("--alerts", nargs="*", default=None,
                    help="explicit SLO alert JSONL paths (default: "
                         "every *alerts*.jsonl under --dir)")
    ap.add_argument("--follow", action="store_true",
                    help="re-render every --interval seconds")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="--follow refresh period (default 2s)")
    ap.add_argument("--iterations", type=int, default=0,
                    help="--follow passes before exiting "
                         "(0 = until interrupted)")
    a = ap.parse_args(argv)

    from singa_tpu import trace

    def one_pass():
        # re-glob each pass: a live fleet creates streams mid-follow
        if a.files is not None:
            paths = list(a.files)
        else:
            paths = sorted(glob.glob(os.path.join(a.dir,
                                                  "*fleet*.jsonl")))
        if a.alerts is not None:
            apaths = list(a.alerts)
        else:
            apaths = sorted(glob.glob(os.path.join(a.dir,
                                                   "*alerts*.jsonl")))
        agg = trace.aggregate_fleet(paths=paths, chrome_trace=a.trace)
        arecs = load_alerts(apaths)
        have_input = bool(agg["requests"] or agg["workers"]
                          or agg["span_count"] or arecs)
        if a.json:
            out = dict(agg)
            if arecs:
                cur = {}
                for r in arecs:
                    cur[(r.get("alert"), r.get("rule"),
                         r.get("replica"))] = r
                act = [r for r in cur.values()
                       if r.get("state") in ("pending", "firing")]
                out["alerts"] = {
                    "transitions": len(arecs),
                    "firing": sum(1 for r in act
                                  if r["state"] == "firing"),
                    "pending": sum(1 for r in act
                                   if r["state"] == "pending"),
                }
            print(json.dumps(out, sort_keys=True))
        else:
            if not have_input:
                print(f"fleet_top: no fleet records under "
                      f"{a.files or a.dir!r} (and no --trace spans)",
                      file=sys.stderr)
                return 1
            body = render(agg, a.events)
            if arecs:
                body += "\n" + "\n".join(alert_panel(arecs))
            print(body)
        return 0 if have_input else 1

    if not a.follow:
        return one_pass()
    it = 0
    rc = 1
    try:
        while True:
            if sys.stdout.isatty():
                print("\x1b[2J\x1b[H", end="")
            rc = one_pass()
            it += 1
            if a.iterations and it >= a.iterations:
                break
            time.sleep(a.interval)
    except KeyboardInterrupt:
        pass
    return rc


if __name__ == "__main__":
    try:
        import signal

        signal.signal(signal.SIGPIPE, signal.SIG_DFL)  # `| head` etc.
    except (ImportError, AttributeError, ValueError):
        pass
    sys.exit(main())
