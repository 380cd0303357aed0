#!/usr/bin/env python3
"""metrics_lint — schema validator for telemetry JSONL (ISSUE 20).

Every telemetry stream this repo writes is schema-stable by contract:
a `MetricsLogger` record (training/serving metrics) and an SLO alert
record each carry a `schema` version and a FIXED key set — fields are
always present, `None` when unknown, and never renamed in place.
Downstream folds (`aggregate_fleet`, `fleet_top`) lean on that
stability, so a drifted writer should fail a lint, not
silently shade a dashboard.

This linter validates streams against the schema-version registry:

  - unknown top-level keys (a writer grew a field without bumping
    the schema version) and missing keys (a writer dropped one)
  - mixed schema versions within one stream (two writer vintages
    appending to the same file)
  - unparseable lines: the at-most-one PARTIAL TRAILING line a
    SIGKILL mid-append leaves is tolerated by design (`read_metrics`
    skips it); garbage anywhere else is an error
  - unknown schema versions / unrecognized stream kinds

Usage:
  tools/metrics_lint.py FILE [FILE ...]     # explicit streams
  tools/metrics_lint.py --dir metrics       # every *.jsonl under dir

Files whose records are neither metrics nor alert records (e.g.
measured-config caches) are reported as skipped, not failed.

Exit codes: 0 = all streams clean, 1 = lint issues, 2 = no input.
"""
import argparse
import glob
import json
import os
import sys

# -- schema registry --------------------------------------------------------
# MetricsLogger v1 (pre-ISSUE 15): no writer pid / monotonic stamp.
_METRICS_V1 = frozenset({
    "schema", "time", "step", "loss", "step_s", "data_wait_s",
    "dispatch_s", "device_sync_s", "examples_per_sec", "cache",
    "resilience", "accum", "metrics", "extra",
})
# MetricsLogger v2 (ISSUE 15): + pid/mono for offline clock alignment.
_METRICS_V2 = _METRICS_V1 | {"pid", "mono"}
# SLO alert stream v1 (ISSUE 20): one record per state transition.
_ALERTS_V1 = frozenset({
    "schema", "kind", "time", "mono", "alert", "rule", "severity",
    "replica", "state", "episode", "burn_long", "burn_short",
    "value", "threshold",
})

_REGISTRY = {
    ("metrics", 1): _METRICS_V1,
    ("metrics", 2): _METRICS_V2,
    ("alerts", 1): _ALERTS_V1,
}


def _classify(rec):
    """Stream family for one record, or None if unrecognized."""
    if rec.get("kind") == "slo_alert":
        return "alerts"
    if "schema" in rec and "step" in rec:
        return "metrics"
    return None


def lint_file(path):
    """(issues, n_records, family) for one stream. `issues` is a list
    of human-readable strings; empty == clean. family is None when
    the stream is not a telemetry stream this registry knows."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            lines = f.readlines()
    except OSError as e:
        return [f"unreadable: {e}"], 0, None
    issues = []
    recs = []
    last_idx = max((i for i, ln in enumerate(lines) if ln.strip()),
                   default=-1)
    for i, ln in enumerate(lines):
        ln = ln.strip()
        if not ln:
            continue
        try:
            rec = json.loads(ln)
        except ValueError:
            if i == last_idx:
                # SIGKILL mid-append leaves at most one torn tail —
                # tolerated by design, every reader skips it
                continue
            issues.append(f"line {i + 1}: unparseable (not the "
                          "trailing line — torn mid-stream)")
            continue
        if not isinstance(rec, dict):
            issues.append(f"line {i + 1}: not a JSON object")
            continue
        recs.append((i + 1, rec))
    if not recs:
        return issues, 0, None
    family = _classify(recs[0][1])
    if family is None:
        return issues, len(recs), None
    seen_schemas = set()
    for lineno, rec in recs:
        fam = _classify(rec)
        if fam != family:
            issues.append(f"line {lineno}: {fam or 'unknown'} record "
                          f"in a {family} stream")
            continue
        ver = rec.get("schema")
        seen_schemas.add(ver)
        keys = _REGISTRY.get((family, ver))
        if keys is None:
            issues.append(f"line {lineno}: unknown {family} schema "
                          f"version {ver!r}")
            continue
        unknown = sorted(set(rec) - keys)
        missing = sorted(keys - set(rec))
        if unknown:
            issues.append(f"line {lineno}: unknown key(s) "
                          f"{', '.join(unknown)} (schema {ver} — "
                          "bump the version to grow the record)")
        if missing:
            issues.append(f"line {lineno}: missing key(s) "
                          f"{', '.join(missing)} (schema-stable "
                          "records carry every field, None when "
                          "unknown)")
    if len(seen_schemas) > 1:
        issues.append(f"mixed schema versions in one stream: "
                      f"{sorted(map(str, seen_schemas))}")
    return issues, len(recs), family


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="lint telemetry JSONL streams against the "
                    "schema-version registry")
    ap.add_argument("files", nargs="*", help="JSONL streams to lint")
    ap.add_argument("--dir", default=None,
                    help="lint every *.jsonl under this directory")
    ap.add_argument("--quiet", action="store_true",
                    help="exit code only")
    a = ap.parse_args(argv)
    paths = list(a.files)
    if a.dir:
        paths += sorted(glob.glob(os.path.join(a.dir, "*.jsonl")))
    if not paths:
        print("metrics_lint: no input files", file=sys.stderr)
        return 2
    bad = 0
    for p in paths:
        issues, n, family = lint_file(p)
        tag = family or "skipped"
        if issues:
            bad += 1
            if not a.quiet:
                print(f"{p}: {tag}, {n} record(s), "
                      f"{len(issues)} issue(s)")
                for msg in issues:
                    print(f"  {msg}")
        elif not a.quiet:
            print(f"{p}: {tag}, {n} record(s), clean")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
