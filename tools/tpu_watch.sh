#!/bin/bash
# Live tails of the metrics JSONL streams a run writes.
#
#   tools/tpu_watch.sh metrics [DIR]   tail the NEWEST metrics JSONL under
#                                      DIR (default: ./metrics, where bench
#                                      stages and MetricsLogger write) and
#                                      print one pretty line per training
#                                      step — live training telemetry
#                                      instead of raw stage logs. Partial
#                                      trailing lines (a run killed
#                                      mid-write) are skipped, matching
#                                      singa_tpu.trace.read_metrics.
#   tools/tpu_watch.sh serve [DIR]     same tail, serving flavor: prefer
#                                      the newest *serve*.jsonl and render
#                                      the per-dispatch serving record
#                                      (requests/rows/bucket, occupancy,
#                                      pad fraction, rolling p50/p99) the
#                                      ServingEngine's MetricsLogger
#                                      stream carries.

#   tools/tpu_watch.sh decode [DIR]    tail the NEWEST *decode*.jsonl under
#                                      DIR and render the decode tier's
#                                      per-dispatch record (fused sessions/
#                                      slots, run-ahead block, slab seq
#                                      rung, occupancy, queue depth) plus
#                                      the session reconciliation counters
#                                      the continuous-batching engine
#                                      streams.

#   tools/tpu_watch.sh fleet [DIR]     tail the NEWEST *fleet*.jsonl under
#                                      DIR and render the FleetRouter's
#                                      records: route events (replica
#                                      picked, state census) and
#                                      transition events (ejections,
#                                      rejoins, restarts) with the
#                                      routed/failover/refused counters —
#                                      the fleet's live control-plane log.

#   tools/tpu_watch.sh fleet-decode [DIR]
#                                      decode flavor of the fleet tail:
#                                      newest *fleet_decode*.jsonl, with
#                                      the session terminals (requests/
#                                      replies/failed), migration/replay
#                                      counters, per-replica KV-slot
#                                      occupancy, and the aggregate
#                                      record's TTFT/TPOT p99 columns.

#   tools/tpu_watch.sh tune [DIR]      tail the NEWEST autotune search
#                                      JSONL under DIR (default:
#                                      ./metrics, where tools/autotune.py
#                                      streams candidates) and print one
#                                      pretty line per scored config —
#                                      live search telemetry.

#   tools/tpu_watch.sh slo [DIR]       tail the NEWEST SLO alert JSONL
#                                      (*alerts*.jsonl) under DIR and
#                                      print one line per alert state
#                                      transition (pending/firing/
#                                      resolved with burn rates) — the
#                                      fleet's live alert feed.

if [ "$1" = "slo" ]; then
  dir=${2:-metrics}
  f=$(ls -t "$dir"/*alerts*.jsonl 2>/dev/null | head -1)
  if [ -z "$f" ]; then
    echo "tpu_watch: no SLO alert JSONL under $dir/ yet" >&2
    exit 1
  fi
  echo "tpu_watch: tailing $f" >&2
  tail -n +1 -F "$f" | python3 -u -c '
import json, sys

for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    try:
        r = json.loads(line)
    except ValueError:
        continue  # partial trailing line from a killed writer
    if not isinstance(r, dict) or r.get("kind") != "slo_alert":
        continue
    state = str(r.get("state", "?"))
    mark = {"pending": "...", "firing": "!!!",
            "resolved": " ok"}.get(state, "  ?")
    bits = [
        mark,
        str(r.get("alert", "?")).ljust(24),
        ("rule " + str(r.get("rule"))).ljust(11),
        str(r.get("severity", "?")).ljust(6),
        "rep " + str(r.get("replica", "-")).ljust(14),
        state.ljust(8),
        "ep " + str(r.get("episode", "?")),
    ]
    if r.get("burn_short") or r.get("burn_long"):
        bits.append("burn " + str(r.get("burn_short")) + "/"
                    + str(r.get("burn_long")))
    if r.get("value") is not None:
        bits.append("v=" + str(r.get("value"))
                    + " thr=" + str(r.get("threshold")))
    print("  ".join(bits))
'
  exit $?
fi

if [ "$1" = "tune" ]; then
  dir=${2:-metrics}
  f=$(ls -t "$dir"/*autotune*.jsonl 2>/dev/null | head -1)
  if [ -z "$f" ]; then
    echo "tpu_watch: no autotune JSONL under $dir/ yet" >&2
    exit 1
  fi
  echo "tpu_watch: tailing $f" >&2
  tail -n +1 -F "$f" | python3 -u -c '
import json, sys

def fmt(v, nd=1):
    if v is None:
        return "-"
    return str(round(v, nd))

def human(b):
    if b is None:
        return "-"
    for unit in ("B", "KB", "MB", "GB"):
        if b < 1024:
            return f"{b:.0f}{unit}"
        b /= 1024.0
    return f"{b:.1f}TB"

for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    try:
        r = json.loads(line)
    except ValueError:
        continue  # partial trailing line from a killed writer
    if not isinstance(r, dict) or "config" not in r:
        continue
    cfg = r.get("config") or {}
    nd = " ".join(f"{k}={v}" for k, v in sorted(cfg.items())
                  if v not in (None, "default", 1))
    bits = [
        "cand " + str(r.get("i", "?")).rjust(3),
        "score " + fmt(r.get("score")).rjust(10),
        "bytes " + human(r.get("bytes")),
        "peak " + human(r.get("peak_bytes")),
        ("cached" if r.get("cached") else r.get("source", "?")),
    ]
    if not r.get("feasible", True):
        bits.append("INFEASIBLE")
    bits.append(nd or "default")
    print("  ".join(bits))
'
  exit $?
fi

if [ "$1" = "fleet-decode" ]; then
  dir=${2:-metrics}
  # the decode-tier router log (bench.py --stage fleet-decode /
  # FleetRouter with decode sessions) is tagged *fleet_decode*;
  # per-WORKER streams (*.worker.jsonl) are data-plane — skip them
  f=$(ls -t "$dir"/*fleet_decode*.jsonl 2>/dev/null | grep -v '\.worker\.jsonl$' | head -1)
  [ -z "$f" ] && f=$(ls -t "$dir"/*fleet_decode*.jsonl 2>/dev/null | head -1)
  if [ -z "$f" ]; then
    echo "tpu_watch: no fleet-decode metrics JSONL under $dir/ yet" >&2
    exit 1
  fi
  echo "tpu_watch: tailing $f" >&2
  tail -n +1 -F "$f" | python3 -u -c '
import json, sys

for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    try:
        r = json.loads(line)
    except ValueError:
        continue  # partial trailing line from a killed writer
    if not isinstance(r, dict):
        continue
    x = r.get("extra") or {}
    if "event" not in x:
        continue  # not a fleet control-plane record
    bits = ["ev " + str(r.get("step", "?")).rjust(5),
            str(x.get("event", "?")).ljust(10)]
    if x.get("replica") is not None:
        bits.append("rep " + str(x["replica"]))
    # session terminals + hand-off counters: the decode router
    # equation (requests == replies + failed + rejected) moving live
    for k, tag in (("decode_requests", "sess"),
                   ("decode_replies", "done"),
                   ("decode_failed", "fail"),
                   ("decode_migrations", "mig"),
                   ("decode_replays", "rpl")):
        if x.get(k):
            bits.append(tag + " " + str(x[k]))
    # per-replica KV-slot occupancy shipped on route/stop records
    rd = x.get("replica_decode") or {}
    for name in sorted(rd):
        d = rd[name] or {}
        # quant mode (ISSUE 19) rides the same heartbeat block; the
        # column renders only when a record carries an armed mode, so
        # pre-19 (and fp32) streams render byte-identically
        q = d.get("quant")
        q = " " + str(q) if q and q != "off" else ""
        bits.append(f"{name} {d.get('active_sessions', 0)}a/"
                    f"{d.get('free_slots', 0)}f "
                    f"{round(d.get('tokens_per_s', 0.0))}tok/s{q}")
    segs = x.get("segments") or {}
    for name in ("ttft", "tpot"):
        s = segs.get(name)
        if s and s.get("p99_ms") is not None:
            bits.append(name + " p99 " + str(s["p99_ms"]) + "ms")
    print("  ".join(bits))
'
  exit $?
fi

if [ "$1" = "fleet" ]; then
  dir=${2:-metrics}
  # fleet control-plane streams are tagged *fleet* (ISSUE 11:
  # FleetRouter's MetricsLogger + bench.py --stage fleet write there);
  # per-WORKER serving streams (*.worker.jsonl) are data-plane — skip
  # them so the newest-file pick lands on the router's log
  f=$(ls -t "$dir"/*fleet*.jsonl 2>/dev/null | grep -v '\.worker\.jsonl$' | head -1)
  [ -z "$f" ] && f=$(ls -t "$dir"/*fleet*.jsonl 2>/dev/null | head -1)
  if [ -z "$f" ]; then
    echo "tpu_watch: no fleet metrics JSONL under $dir/ yet" >&2
    exit 1
  fi
  echo "tpu_watch: tailing $f" >&2
  tail -n +1 -F "$f" | python3 -u -c '
import json, sys

for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    try:
        r = json.loads(line)
    except ValueError:
        continue  # partial trailing line from a killed writer
    if not isinstance(r, dict):
        continue
    x = r.get("extra") or {}
    if "event" not in x:
        continue  # not a fleet control-plane record
    states = x.get("states") or {}
    census = " ".join(f"{k}={v}" for k, v in sorted(states.items()))
    bits = ["ev " + str(r.get("step", "?")).rjust(5),
            str(x.get("event", "?")).ljust(10)]
    if x.get("replica") is not None:
        bits.append("rep " + str(x["replica"]))
    if x.get("to_state") is not None:
        bits.append("-> " + str(x["to_state"])
                    + (" (" + str(x.get("reason", "")) + ")"
                       if x.get("reason") else ""))
    bits.append("[" + census + "]")
    # net-fault columns (ISSUE 18) render ONLY when the record
    # carries them (tcp transport + --net-faults); older records
    # print exactly as before
    for k in ("routed", "failovers", "refused", "rejected",
              "ejections", "rejoins", "restarts", "kills_injected",
              "pipe_stalls_injected", "torn_frames_injected",
              "net_faults_injected", "net_partitions_injected"):
        if x.get(k):
            bits.append(k + " " + str(x[k]))
    # per-segment latency columns (ISSUE 15): rendered ONLY when the
    # record carries them (the aggregate record trace.aggregate_fleet
    # appends); pre-trace records print exactly as before
    segs = x.get("segments") or {}
    for name in ("queue_wait", "ipc", "dispatch", "reply"):
        s = segs.get(name)
        if s and s.get("p99_ms") is not None:
            bits.append(name + " p99 " + str(s["p99_ms"]) + "ms")
    if x.get("availability_pct") is not None:
        bits.append("avail " + str(x["availability_pct"]) + "%")
    print("  ".join(bits))
'
  exit $?
fi

if [ "$1" = "parallel" ]; then
  dir=${2:-metrics}
  # multi-axis trainer streams are tagged *parallel* (ISSUE 10:
  # bench.py --stage parallel appends per-block records there)
  f=$(ls -t "$dir"/*parallel*.jsonl 2>/dev/null | head -1)
  if [ -z "$f" ]; then
    echo "tpu_watch: no parallel metrics JSONL under $dir/ yet" >&2
    exit 1
  fi
  echo "tpu_watch: tailing $f" >&2
  tail -n +1 -F "$f" | python3 -u -c '
import json, sys

for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    try:
        r = json.loads(line)
    except ValueError:
        continue  # partial trailing line from a killed writer
    if not isinstance(r, dict):
        continue
    x = r.get("extra") or {}
    arm = x.get("arm", "?")
    bits = ["step " + str(r.get("step", "?")).rjust(5),
            "arm " + str(arm),
            "loss " + str(r.get("loss")),
            "ex/s " + str(round(r.get("examples_per_sec", 0)))]
    if arm == "pipeline":
        bits.append(f"P={x.get('pipe')} M={x.get('microbatches')} "
                    f"{x.get('schedule')}")
    elif arm == "moe":
        bits.append(f"E={x.get('experts')} dropped "
                    f"{x.get('dropped_frac')}")
    print("  ".join(bits))
'
  exit $?
fi

# NOTE: this block must stay ABOVE the serve flavor — serve's
# *serve*.jsonl glob also matches bench_serve_decode.jsonl.
if [ "$1" = "decode" ]; then
  dir=${2:-metrics}
  # *decode*.jsonl also matches the fleet-decode ROUTER streams
  # (bench_fleet_decode*.jsonl, ISSUE 17) — those are control-plane
  # records with their own flavor above; keep this tail on the
  # engine's per-dispatch stream
  f=$(ls -t "$dir"/*decode*.jsonl 2>/dev/null | grep -v fleet | head -1)
  [ -z "$f" ] && f=$(ls -t "$dir"/*decode*.jsonl 2>/dev/null | head -1)
  if [ -z "$f" ]; then
    echo "tpu_watch: no decode metrics JSONL under $dir/ yet" >&2
    exit 1
  fi
  echo "tpu_watch: tailing $f" >&2
  tail -n +1 -F "$f" | python3 -u -c '
import json, sys

def fmt(v, nd=3):
    if v is None:
        return "-"
    return str(round(v, nd))

for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    try:
        r = json.loads(line)
    except ValueError:
        continue  # partial trailing line from a killed writer
    if not isinstance(r, dict):
        continue
    x = r.get("extra") or {}
    bits = [
        "dispatch " + str(r.get("step", "?")).rjust(6),
        "sess " + str(x.get("sessions", "-")) + "/" + str(x.get("slots", "-")),
        "block " + fmt(x.get("block"), 0),
        "seq " + fmt(x.get("slab_seq"), 0),
        "occ " + fmt(x.get("occupancy"), 2),
        "q " + fmt(x.get("queue_depth"), 0),
        "tok/s " + fmt(r.get("examples_per_sec"), 0),
        "toks " + fmt(x.get("tokens_streamed"), 0),
    ]
    # session reconciliation counters: completed + expired + shed +
    # failed — streamed so the tail shows the balance moving live
    for k in ("completed", "expired", "shed", "failed"):
        if k in x:
            bits.append(k + " " + fmt(x.get(k), 0))
    # quant column (ISSUE 19): log_step stamps it only when armed,
    # so pre-19 and fp32 streams render byte-identically
    if x.get("quant"):
        bits.append("quant " + str(x["quant"]))
    print("  ".join(bits))
'
  exit $?
fi

if [ "$1" = "serve" ]; then
  dir=${2:-metrics}
  # serving streams are tagged *serve*; fall back to the newest JSONL
  f=$(ls -t "$dir"/*serve*.jsonl 2>/dev/null | head -1)
  [ -z "$f" ] && f=$(ls -t "$dir"/*.jsonl 2>/dev/null | head -1)
  if [ -z "$f" ]; then
    echo "tpu_watch: no serving metrics JSONL under $dir/ yet" >&2
    exit 1
  fi
  echo "tpu_watch: tailing $f" >&2
  tail -n +1 -F "$f" | python3 -u -c '
import json, sys

def fmt(v, nd=3):
    if v is None:
        return "-"
    return str(round(v, nd))

for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    try:
        r = json.loads(line)
    except ValueError:
        continue  # partial trailing line from a killed writer
    if not isinstance(r, dict):
        continue
    x = r.get("extra") or {}
    bits = [
        "dispatch " + str(r.get("step", "?")).rjust(6),
        "req " + fmt(x.get("requests"), 0),
        "rows " + str(x.get("rows", "-")) + "/" + str(x.get("bucket", "-")),
        "occ " + fmt(x.get("occupancy"), 2),
        "pad " + fmt(x.get("pad_fraction"), 2),
        "q " + fmt(x.get("queue_depth"), 0),
        "req/s " + fmt(r.get("examples_per_sec"), 1),
        "p50 " + fmt(x.get("p50_ms"), 2) + "ms",
        "p99 " + fmt(x.get("p99_ms"), 2) + "ms",
    ]
    # resilience counters (ISSUE 8): rendered only when the record
    # carries them, so pre-resilience JSONL logs render unchanged
    for k in ("expired", "shed", "retries", "failed"):
        if k in x:
            bits.append(k + " " + fmt(x.get(k), 0))
    print("  ".join(bits))
'
  exit $?
fi

if [ "$1" = "metrics" ]; then
  dir=${2:-metrics}
  f=$(ls -t "$dir"/*.jsonl 2>/dev/null | head -1)
  if [ -z "$f" ]; then
    echo "tpu_watch: no metrics JSONL under $dir/ yet" >&2
    exit 1
  fi
  echo "tpu_watch: tailing $f" >&2
  tail -n +1 -F "$f" | python3 -u -c '
import json, sys

def fmt(v, nd=3):
    if v is None:
        return "-"
    return str(round(v, nd))

for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    try:
        r = json.loads(line)
    except ValueError:
        continue  # partial trailing line from a killed writer
    if not isinstance(r, dict):
        continue  # valid JSON but not a record: skip, like read_metrics
    cache = r.get("cache") or {}
    retr = sum(c.get("retraces", 0) for c in cache.values()
               if isinstance(c, dict))
    res = r.get("resilience") or {}
    bits = [
        "step " + str(r.get("step", "?")).rjust(6),
        "loss " + fmt(r.get("loss"), 4),
        "ex/s " + fmt(r.get("examples_per_sec"), 1),
        "step_s " + fmt(r.get("step_s"), 4),
        "wait " + fmt(r.get("data_wait_s"), 4),
        "disp " + fmt(r.get("dispatch_s"), 4),
        "sync " + fmt(r.get("device_sync_s"), 4),
        "retraces " + str(retr),
    ]
    if res.get("steps_skipped"):
        bits.append("skipped " + str(res["steps_skipped"]))
    mets = {k: v for k, v in (r.get("metrics") or {}).items()
            if v is not None}
    for k, v in sorted(mets.items()):
        bits.append(k + " " + fmt(v, 4))
    print("  ".join(bits))
'
  exit $?
fi

echo "usage: tools/tpu_watch.sh metrics|serve|decode|fleet|fleet-decode|parallel|tune|slo [DIR]" >&2
exit 2
