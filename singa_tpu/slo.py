"""Online SLO engine (ISSUE 20): mergeable streaming quantile
sketches, multi-window burn-rate alerting, and per-replica anomaly
detection.

Everything the fleet previously knew about its own latency was
post-hoc: `trace.aggregate_fleet` re-reads metrics JSONL after the
run and sorts raw samples.  This module computes the same surface
*online*, in bounded memory, and mergeable across hosts:

1. **`QuantileSketch`** — a DDSketch-style relative-error sketch.
   Values map to log-spaced buckets ``idx = ceil(log(v)/log(gamma))``
   with ``gamma = (1+rel_err)/(1-rel_err)``, so any reported quantile
   is within ``rel_err`` (relative) of the true sample quantile.
   The bucket *count* is bounded by a canonical **range-based
   collapse**: the kept index range is always
   ``[max_idx - max_buckets + 1, max_idx]`` and samples below the
   floor are clamped up to it (counted loudly in ``collapsed``).
   Because the floor is a pure function of the sample multiset
   (``max`` is associative and commutative), the final bucket state
   is too — which is what makes ``merge()`` exact: merging per-worker
   sketches is *bit-identical* to one sketch fed every sample, in any
   merge order.  The reconciliation-equation discipline, applied to
   percentiles.

2. **`SLOSpec` + burn-rate alerting** — a declarative spec
   (availability target + per-segment latency objectives) evaluated
   continuously over sliding windows using the Google-SRE
   multi-window multi-burn-rate recipe: a *fast* rule (1h long / 5m
   short, burn 14.4, severity ``page``) and a *slow* rule (3d long /
   6h short, burn 1.0, severity ``ticket``), both windows required to
   breach before an alert moves.  A ``window_scale`` knob shrinks the
   canonical windows to bench timescales.  Alerts run a
   pending -> firing -> resolved state machine with flap suppression
   (a blip that never survives the pending hold resolves without
   ever firing) and write schema-stable JSONL records.

3. **Per-replica anomaly detectors** riding signals the fleet
   already produces: heartbeat-gap vs a trailing EWMA baseline,
   clock offset outside the transport's own uncertainty estimate,
   and counter-rate spikes (restarts / refusals / failures /
   failovers / ...) vs a trailing baseline — each surfaced as an
   alert that *names the offending replica*.

Discipline (PR 5 / PR 15): when disabled, ``observe()`` is two
attribute loads and a return — zero allocation, tracemalloc-
verifiable — and worker heartbeats carry **no** ``slo`` key at all
(byte-absent, not empty).  ``configure(enabled=True, ...)`` rebuilds
the engine FRESH (documented reset semantics — bench uses this to
separate its clean and chaos arms).
"""

from __future__ import annotations

import json
import math
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from . import stats as stats_mod

# ---------------------------------------------------------------------------
# Quantile sketch
# ---------------------------------------------------------------------------

ALERTS_SCHEMA = 1


class QuantileSketch:
    """Mergeable relative-error streaming quantile sketch.

    ``add(v)`` buckets ``v`` (ms, or any positive unit) at
    ``ceil(log(v)/log(gamma))``; ``quantile(q)`` walks the buckets and
    returns the bucket's canonical midpoint ``2*gamma**i/(gamma+1)``,
    guaranteeing relative error <= ``rel_err``.  Non-positive values
    land in a dedicated ``zeros`` counter (exact).

    Bounded memory: at most ``max_buckets`` live buckets.  The kept
    range is canonical — ``floor = max_idx - max_buckets + 1`` — and
    mass below the floor is clamped up to the floor bucket and counted
    in ``collapsed`` (loud, never silent).  Collapse therefore biases
    only the *low* tail upward, never the high quantiles operators
    page on.  Because ``max`` is associative/commutative, the final
    state is a pure function of the sample multiset: ``merge()`` of
    any partition of a stream, in any order, is bit-identical to one
    sketch fed the whole stream.
    """

    __slots__ = ("rel_err", "max_buckets", "gamma", "_lg", "buckets",
                 "zeros", "count", "collapsed", "max_value")

    def __init__(self, rel_err: float = 0.02, max_buckets: int = 512):
        if not (0.0 < rel_err < 1.0):
            raise ValueError(f"rel_err must be in (0, 1): {rel_err}")
        if max_buckets < 2:
            raise ValueError(f"max_buckets must be >= 2: {max_buckets}")
        self.rel_err = float(rel_err)
        self.max_buckets = int(max_buckets)
        self.gamma = (1.0 + self.rel_err) / (1.0 - self.rel_err)
        self._lg = math.log(self.gamma)
        self.buckets: Dict[int, int] = {}
        self.zeros = 0
        self.count = 0
        self.collapsed = 0
        self.max_value = 0.0

    # -- write paths ------------------------------------------------------
    def _index(self, v: float) -> int:
        return int(math.ceil(math.log(v) / self._lg))

    def _floor(self) -> Optional[int]:
        if not self.buckets:
            return None
        return max(self.buckets) - self.max_buckets + 1

    def add(self, v: float, n: int = 1) -> None:
        if n <= 0:
            return
        self.count += n
        if v > self.max_value:
            self.max_value = float(v)
        if v <= 0.0:
            self.zeros += n
            return
        idx = self._index(v)
        if not self.buckets:
            self.buckets[idx] = n
            return
        hi = max(self.buckets)
        floor = hi - self.max_buckets + 1
        if idx < floor:
            # below the kept range: clamp up to the floor, loudly
            self.buckets[floor] = self.buckets.get(floor, 0) + n
            self.collapsed += n
            return
        self.buckets[idx] = self.buckets.get(idx, 0) + n
        if idx > hi:
            # the max rose, so the canonical floor rose with it —
            # fold EAGERLY (even while under the bucket budget), or
            # the state stops being a pure function of the multiset
            # and merge() stops being exact
            new_floor = idx - self.max_buckets + 1
            if min(self.buckets) < new_floor:
                self._fold_below(new_floor)

    def _fold_below(self, floor: int) -> None:
        """Fold all mass at indices < ``floor`` into the floor
        bucket.  Every folded sample's true index is <= its stored
        index < floor, so the folded mass is EXACTLY the set of
        samples whose true index is below the new floor: previously-
        collapsed mass always sits at the old floor (< the new one)
        and folds along, so ``collapsed = folded`` restores the
        invariant ``collapsed == #samples with true index < floor``
        without double counting."""
        folded = 0
        for k in [k for k in self.buckets if k < floor]:
            folded += self.buckets.pop(k)
        if folded:
            self.buckets[floor] = self.buckets.get(floor, 0) + folded
            self.collapsed = folded

    def merge(self, other: "QuantileSketch") -> None:
        """Exact merge: after this call, state is bit-identical to a
        single sketch fed both sample streams (any order)."""
        if (other.rel_err != self.rel_err
                or other.max_buckets != self.max_buckets):
            raise ValueError(
                "sketch shape mismatch: cannot merge "
                f"rel_err={other.rel_err}/buckets={other.max_buckets} "
                f"into rel_err={self.rel_err}/buckets={self.max_buckets}")
        self.count += other.count
        self.zeros += other.zeros
        if other.max_value > self.max_value:
            self.max_value = other.max_value
        if not other.buckets:
            self.collapsed += other.collapsed  # zeros-only side
            return
        hi = max(max(self.buckets) if self.buckets else -(1 << 60),
                 max(other.buckets))
        floor = hi - self.max_buckets + 1
        self_floor = self._floor()
        other_floor = other._floor()
        # Fold each side's sub-floor mass; a side's previously-
        # collapsed mass is already inside its sub-floor mass UNLESS
        # that side's floor survives as the merged floor, in which
        # case it folds nothing and its collapsed count carries over.
        new_collapsed = 0
        new_buckets: Dict[int, int] = {}
        for side, side_floor in ((self, self_floor),
                                 (other, other_floor)):
            folded = 0
            for k, c in side.buckets.items():
                if k < floor:
                    folded += c
                else:
                    new_buckets[k] = new_buckets.get(k, 0) + c
            if folded:
                new_buckets[floor] = new_buckets.get(floor, 0) + folded
                new_collapsed += folded
            elif side_floor is not None and side_floor >= floor:
                new_collapsed += side.collapsed
        self.buckets = new_buckets
        self.collapsed = new_collapsed

    # -- read paths -------------------------------------------------------
    def _value(self, idx: int) -> float:
        return 2.0 * (self.gamma ** idx) / (self.gamma + 1.0)

    def quantile(self, q: float) -> Optional[float]:
        """Sample quantile at ``q`` in [0, 1] under the rank
        convention ``rank = q * (count - 1)``, first bucket whose
        cumulative count exceeds ``rank`` — the convention
        ``trace.fleet_segment_samples_ms`` consumers must mirror when
        cross-validating (bench gates on it)."""
        if self.count == 0:
            return None
        rank = q * (self.count - 1)
        if self.zeros > rank:
            return 0.0
        cum = self.zeros
        for k in sorted(self.buckets):
            cum += self.buckets[k]
            if cum > rank:
                return self._value(k)
        return self._value(max(self.buckets)) if self.buckets else 0.0

    def snapshot(self) -> Dict:
        """Schema-stable summary (every key always present)."""
        return {
            "count": self.count,
            "zeros": self.zeros,
            "collapsed": self.collapsed,
            "p50_ms": round(self.quantile(0.50) or 0.0, 3),
            "p90_ms": round(self.quantile(0.90) or 0.0, 3),
            "p99_ms": round(self.quantile(0.99) or 0.0, 3),
            "max_ms": round(self.max_value, 3),
        }

    # -- wire -------------------------------------------------------------
    def to_wire(self) -> Dict:
        ks = sorted(self.buckets)
        return {"e": self.rel_err, "b": self.max_buckets,
                "n": self.count, "z": self.zeros, "c": self.collapsed,
                "m": self.max_value, "k": ks,
                "v": [self.buckets[k] for k in ks]}

    @classmethod
    def from_wire(cls, w: Dict) -> "QuantileSketch":
        sk = cls(rel_err=float(w["e"]), max_buckets=int(w["b"]))
        sk.count = int(w["n"])
        sk.zeros = int(w["z"])
        sk.collapsed = int(w["c"])
        sk.max_value = float(w["m"])
        sk.buckets = {int(k): int(c) for k, c in zip(w["k"], w["v"])}
        return sk

    def copy(self) -> "QuantileSketch":
        sk = QuantileSketch(self.rel_err, self.max_buckets)
        sk.count = self.count
        sk.zeros = self.zeros
        sk.collapsed = self.collapsed
        sk.max_value = self.max_value
        sk.buckets = dict(self.buckets)
        return sk


def rank_quantile(sorted_samples, q: float):
    """`QuantileSketch.quantile`'s rank convention applied to raw
    sorted samples: ``rank = q * (n - 1)``, value = first sample
    whose cumulative count exceeds ``rank`` (= ``sorted[floor(rank)]``).
    A cross-validation of the sketch against raw samples compares it
    with THIS, not with `np.percentile`'s interpolation — at small n
    the interpolation disagrees by more than the sketch's documented
    relative-error bound and would fail the gate spuriously."""
    n = len(sorted_samples)
    if n == 0:
        return None
    return sorted_samples[int(math.floor(q * (n - 1)))]


# ---------------------------------------------------------------------------
# Spec + burn rules
# ---------------------------------------------------------------------------

# Google-SRE multi-window multi-burn-rate recipe (SRE Workbook ch. 5),
# canonical (unscaled) windows in seconds.  `window_scale` multiplies
# long_s/short_s so bench runs (seconds, not days) exercise the same
# machinery end to end.
BURN_RULES = (
    {"name": "fast", "long_s": 3600.0, "short_s": 300.0,
     "burn": 14.4, "severity": "page"},
    {"name": "slow", "long_s": 259200.0, "short_s": 21600.0,
     "burn": 1.0, "severity": "ticket"},
)


@dataclass
class SLOSpec:
    """Declarative SLO: an availability target plus per-segment
    latency objectives.  A latency objective is the SRE-style
    request-based form — "fraction of samples <= threshold_ms must be
    >= target" — which reduces latency to a good/bad event stream the
    same burn-rate rules evaluate."""
    availability: float = 0.999
    latency: Dict[str, Dict[str, float]] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d) -> "SLOSpec":
        if isinstance(d, SLOSpec):
            return d
        d = dict(d or {})
        lat = {}
        for seg, obj in (d.get("latency") or {}).items():
            lat[str(seg)] = {"threshold_ms": float(obj["threshold_ms"]),
                             "target": float(obj.get("target", 0.99))}
        return cls(availability=float(d.get("availability", 0.999)),
                   latency=lat)

    def to_dict(self) -> Dict:
        return {"availability": self.availability,
                "latency": {k: dict(v) for k, v in self.latency.items()}}


class _WindowedCounter:
    """Good/bad event counts over sliding windows, bounded memory:
    events coarsen into time buckets of width ``gran_s`` and retention
    is capped at the longest window anyone will ask about."""

    __slots__ = ("gran", "max_s", "buckets", "good", "bad")

    def __init__(self, gran_s: float, max_s: float):
        self.gran = max(float(gran_s), 1e-4)
        self.max_s = float(max_s)
        self.buckets: deque = deque()  # (t_quantized, good, bad)
        self.good = 0
        self.bad = 0

    def add(self, ok: bool, now: float) -> None:
        tq = math.floor(now / self.gran) * self.gran
        g, b = (1, 0) if ok else (0, 1)
        self.good += g
        self.bad += b
        if self.buckets and self.buckets[-1][0] == tq:
            t, pg, pb = self.buckets[-1]
            self.buckets[-1] = (t, pg + g, pb + b)
        else:
            self.buckets.append((tq, g, b))
        self._prune(now)

    def _prune(self, now: float) -> None:
        cutoff = now - self.max_s - self.gran
        while self.buckets and self.buckets[0][0] < cutoff:
            self.buckets.popleft()

    def window(self, window_s: float, now: float) -> Tuple[int, int]:
        cutoff = now - window_s
        g = b = 0
        for t, wg, wb in reversed(self.buckets):
            if t < cutoff:
                break
            g += wg
            b += wb
        return g, b


def _burn(good: int, bad: int, target: float) -> float:
    """Error-budget burn rate: observed bad fraction over the budget
    ``1 - target``.  Empty window burns nothing (0.0) — which is what
    lets alerts resolve once the window drains."""
    n = good + bad
    if n == 0:
        return 0.0
    return (bad / n) / max(1.0 - target, 1e-9)


# ---------------------------------------------------------------------------
# Alert state machine
# ---------------------------------------------------------------------------

class _AlertState:
    """inactive -> pending -> firing -> resolved (-> inactive).

    Flap suppression: a breach must hold for ``pending_for`` before
    firing, and a recovery must hold for ``resolve_for`` before
    resolving.  A blip shorter than the pending hold goes
    pending -> resolved without ever firing — recorded, but it never
    paged anyone."""

    __slots__ = ("alert", "rule", "severity", "replica", "state",
                 "t_enter", "t_last_ok", "episode")

    def __init__(self, alert: str, rule: str, severity: str,
                 replica: str):
        self.alert = alert
        self.rule = rule
        self.severity = severity
        self.replica = replica
        self.state = "inactive"
        self.t_enter = 0.0
        self.t_last_ok = 0.0
        self.episode = 0

    def step(self, now: float, breach: bool, pending_for: float,
             resolve_for: float) -> List[str]:
        """Advance one tick; returns the transition names emitted
        (subset of {"pending", "firing", "resolved"})."""
        out: List[str] = []
        if self.state == "inactive":
            if breach:
                self.state = "pending"
                self.t_enter = now
                self.t_last_ok = now
                self.episode += 1
                out.append("pending")
            return out
        if breach:
            self.t_last_ok = now  # recovery clock restarts
            if (self.state == "pending"
                    and now - self.t_enter >= pending_for):
                self.state = "firing"
                self.t_enter = now
                out.append("firing")
            return out
        if now - self.t_last_ok >= resolve_for:
            self.state = "inactive"
            out.append("resolved")
        return out


# ---------------------------------------------------------------------------
# Anomaly detectors
# ---------------------------------------------------------------------------

_SPIKE_MIN = {"restarts": 1, "failures": 3, "refusals": 5,
              "failovers": 2, "rejected": 5, "retries": 10,
              "shed": 5, "expired": 3}
_SPIKE_MIN_DEFAULT = 5


class _HbGapDetector:
    """Heartbeat-gap EWMA: breach when the observed gap exceeds
    ``max(min_s, mult * baseline)``.  The baseline only learns while
    healthy — a dead worker's growing gap never drags the baseline up
    after it."""

    __slots__ = ("ewma", "mult", "min_s", "alpha")

    def __init__(self, mult: float, min_s: float):
        self.ewma: Optional[float] = None
        self.mult = mult
        self.min_s = min_s
        self.alpha = 0.2

    def update(self, gap_s: float) -> Tuple[bool, float]:
        if self.ewma is None:
            self.ewma = gap_s
            return False, max(self.min_s, self.mult * gap_s)
        thr = max(self.min_s, self.mult * self.ewma)
        breach = gap_s > thr
        if not breach:
            self.ewma = (self.alpha * gap_s
                         + (1.0 - self.alpha) * self.ewma)
        return breach, thr


class _SpikeDetector:
    """Counter-rate spike vs trailing baseline: deltas of a cumulative
    counter accumulate over a short trailing window; breach when the
    windowed total exceeds ``max(min_count, mult * baseline)`` where
    the baseline is an EWMA of the windowed total learned only while
    healthy."""

    __slots__ = ("last", "events", "ewma", "window_s", "mult",
                 "min_count", "alpha")

    def __init__(self, window_s: float, mult: float, min_count: int):
        self.last: Optional[float] = None
        self.events: deque = deque()  # (t, delta)
        self.ewma = 0.0
        self.window_s = window_s
        self.mult = mult
        self.min_count = min_count
        self.alpha = 0.2

    def update(self, now: float, value: float) -> Tuple[bool, float]:
        if self.last is None:
            self.last = value
            return False, 0.0
        d = value - self.last
        self.last = value
        if d < 0:
            self.events.clear()  # counter reset upstream
            d = 0.0
        if d > 0:
            self.events.append((now, d))
        cutoff = now - self.window_s
        while self.events and self.events[0][0] < cutoff:
            self.events.popleft()
        w = sum(d for _, d in self.events)
        breach = w >= max(float(self.min_count),
                          self.mult * self.ewma)
        if not breach:
            self.ewma = self.alpha * w + (1.0 - self.alpha) * self.ewma
        return breach, w


# ---------------------------------------------------------------------------
# Counters (cache_stats()["slo"])
# ---------------------------------------------------------------------------

class _SLOStats:
    __slots__ = ("observed", "outcomes_good", "outcomes_bad", "ticks",
                 "ingests", "ingests_stale", "alerts_emitted",
                 "collapse_events")

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.observed = 0
        self.outcomes_good = 0
        self.outcomes_bad = 0
        self.ticks = 0
        self.ingests = 0
        self.ingests_stale = 0
        self.alerts_emitted = 0
        self.collapse_events = 0

    def snapshot(self) -> Dict:
        return {"enabled": int(enabled()),
                "observed": self.observed,
                "outcomes_good": self.outcomes_good,
                "outcomes_bad": self.outcomes_bad,
                "ticks": self.ticks,
                "ingests": self.ingests,
                "ingests_stale": self.ingests_stale,
                "alerts_emitted": self.alerts_emitted,
                "collapse_events": self.collapse_events}


_STATS = _SLOStats()
stats_mod.register_cache("slo", _STATS)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class _Engine:
    def __init__(self, *, rel_err: float, max_buckets: int,
                 window_scale: float, spec: SLOSpec,
                 alerts_path: Optional[str],
                 hb_gap_mult: float, hb_gap_min_s: float,
                 clock_mult: float, clock_slack_us: float,
                 spike_window_s: float, spike_mult: float,
                 anomaly_pending_s: float, anomaly_resolve_s: float):
        self.rel_err = rel_err
        self.max_buckets = max_buckets
        self.window_scale = window_scale
        self.spec = spec
        self.alerts_path = alerts_path
        self.hb_gap_mult = hb_gap_mult
        self.hb_gap_min_s = hb_gap_min_s
        self.clock_mult = clock_mult
        self.clock_slack_us = clock_slack_us
        self.spike_window_s = spike_window_s
        self.spike_mult = spike_mult
        self.anomaly_pending_s = anomaly_pending_s
        self.anomaly_resolve_s = anomaly_resolve_s
        self.rules = [dict(r, long_s=r["long_s"] * window_scale,
                           short_s=r["short_s"] * window_scale)
                      for r in BURN_RULES]
        max_long = max(r["long_s"] for r in self.rules)
        min_short = min(r["short_s"] for r in self.rules)
        self._gran = max(min_short / 8.0, 1e-3)
        self._max_win = max_long
        self._lock = threading.RLock()
        self.sketches: Dict[str, QuantileSketch] = {}
        self.availability = _WindowedCounter(self._gran, self._max_win)
        self.latency_win: Dict[str, _WindowedCounter] = {
            seg: _WindowedCounter(self._gran, self._max_win)
            for seg in spec.latency}
        self.peers: Dict[str, Dict] = {}  # replica -> {gen, seg}
        self.alert_states: Dict[Tuple[str, str, str], _AlertState] = {}
        self.recent_alerts: deque = deque(maxlen=256)
        self._alerts_fh = None
        self._resolved_total = 0

    # -- feeds ------------------------------------------------------------
    def observe(self, segment: str, seconds: float,
                now: Optional[float]) -> None:
        ms = seconds * 1e3
        t = time.monotonic() if now is None else now
        with self._lock:
            sk = self.sketches.get(segment)
            if sk is None:
                sk = QuantileSketch(self.rel_err, self.max_buckets)
                self.sketches[segment] = sk
            before = sk.collapsed
            sk.add(ms)
            if sk.collapsed > before:
                _STATS.collapse_events += 1
            obj = self.spec.latency.get(segment)
            if obj is not None:
                self.latency_win[segment].add(
                    ms <= obj["threshold_ms"], t)
            _STATS.observed += 1

    def observe_outcome(self, ok: bool, now: Optional[float]) -> None:
        t = time.monotonic() if now is None else now
        with self._lock:
            self.availability.add(ok, t)
            if ok:
                _STATS.outcomes_good += 1
            else:
                _STATS.outcomes_bad += 1

    # -- wire -------------------------------------------------------------
    def wire_payload(self) -> Dict:
        with self._lock:
            return {"seg": {name: sk.to_wire()
                            for name, sk in self.sketches.items()}}

    def ingest_wire(self, replica: str, payload: Dict,
                    gen: int) -> None:
        seg = (payload or {}).get("seg")
        if not isinstance(seg, dict):
            return
        with self._lock:
            prev = self.peers.get(replica)
            if prev is not None and gen < prev["gen"]:
                _STATS.ingests_stale += 1
                return
            # cumulative last-writer-wins per (replica, generation):
            # replace, never accumulate — idempotent under heartbeat
            # loss, duplication, and reconnect
            self.peers[replica] = {"gen": gen, "seg": seg}
            _STATS.ingests += 1

    def merged_sketches(self) -> Dict[str, QuantileSketch]:
        with self._lock:
            out = {name: sk.copy()
                   for name, sk in self.sketches.items()}
            for rep in sorted(self.peers):
                for name, w in self.peers[rep]["seg"].items():
                    sk = QuantileSketch.from_wire(w)
                    if name in out:
                        out[name].merge(sk)
                    else:
                        out[name] = sk
            return out

    # -- anomaly feed -----------------------------------------------------
    def note_replica(self, name: str, *, hb_gap_s=None,
                     clock_offset_us=None, clock_uncertainty_us=None,
                     counters=None, now: Optional[float]) -> None:
        t = time.monotonic() if now is None else now
        with self._lock:
            if hb_gap_s is not None:
                det = self._detector(
                    ("hb", name), lambda: _HbGapDetector(
                        self.hb_gap_mult, self.hb_gap_min_s))
                breach, thr = det.update(float(hb_gap_s))
                self._step_anomaly("anomaly:hb_gap", name, t, breach,
                                   value=float(hb_gap_s),
                                   threshold=thr)
            if (clock_offset_us is not None
                    and clock_uncertainty_us is not None):
                thr = (abs(float(clock_uncertainty_us))
                       * self.clock_mult + self.clock_slack_us)
                breach = abs(float(clock_offset_us)) > thr
                self._step_anomaly("anomaly:clock", name, t, breach,
                                   value=float(clock_offset_us),
                                   threshold=thr)
            for cname, val in sorted((counters or {}).items()):
                det = self._detector(
                    ("rate", name, cname),
                    lambda c=cname: _SpikeDetector(
                        self.spike_window_s, self.spike_mult,
                        _SPIKE_MIN.get(c, _SPIKE_MIN_DEFAULT)))
                breach, w = det.update(t, float(val))
                self._step_anomaly(f"anomaly:rate:{cname}", name, t,
                                   breach, value=w,
                                   threshold=float(
                                       _SPIKE_MIN.get(
                                           cname,
                                           _SPIKE_MIN_DEFAULT)))

    def _detector(self, key, mk):
        d = getattr(self, "_detectors", None)
        if d is None:
            d = self._detectors = {}
        det = d.get(key)
        if det is None:
            det = d[key] = mk()
        return det

    def _step_anomaly(self, alert: str, replica: str, now: float,
                      breach: bool, *, value: float,
                      threshold: float) -> None:
        st = self._state(alert, "-", "page", replica)
        for tr in st.step(now, breach, self.anomaly_pending_s,
                          self.anomaly_resolve_s):
            self._emit(st, tr, now, burn_long=0.0, burn_short=0.0,
                       value=value, threshold=threshold)

    # -- evaluation -------------------------------------------------------
    def _state(self, alert: str, rule: str, severity: str,
               replica: str) -> _AlertState:
        key = (alert, rule, replica)
        st = self.alert_states.get(key)
        if st is None:
            st = _AlertState(alert, rule, severity, replica)
            self.alert_states[key] = st
        return st

    def tick(self, now: Optional[float]) -> None:
        t = time.monotonic() if now is None else now
        with self._lock:
            _STATS.ticks += 1
            objectives = [("availability", self.availability,
                           self.spec.availability)]
            for seg, obj in self.spec.latency.items():
                objectives.append((f"latency:{seg}",
                                   self.latency_win[seg],
                                   obj["target"]))
            for alert, win, target in objectives:
                for rule in self.rules:
                    gl, bl = win.window(rule["long_s"], t)
                    gs, bs = win.window(rule["short_s"], t)
                    burn_long = _burn(gl, bl, target)
                    burn_short = _burn(gs, bs, target)
                    breach = (burn_long >= rule["burn"]
                              and burn_short >= rule["burn"])
                    st = self._state(alert, rule["name"],
                                     rule["severity"], "-")
                    pend = max(0.1, 0.5 * rule["short_s"])
                    reslv = max(0.25, 1.0 * rule["short_s"])
                    for tr in st.step(t, breach, pend, reslv):
                        self._emit(st, tr, t, burn_long=burn_long,
                                   burn_short=burn_short,
                                   value=burn_long,
                                   threshold=rule["burn"])

    # -- emission ---------------------------------------------------------
    def _emit(self, st: _AlertState, transition: str, now: float, *,
              burn_long: float, burn_short: float, value: float,
              threshold: float) -> None:
        rec = {"schema": ALERTS_SCHEMA, "kind": "slo_alert",
               "time": time.time(), "mono": round(now, 6),
               "alert": st.alert, "rule": st.rule,
               "severity": st.severity, "replica": st.replica,
               "state": transition, "episode": st.episode,
               "burn_long": round(burn_long, 4),
               "burn_short": round(burn_short, 4),
               "value": round(value, 4),
               "threshold": round(threshold, 4)}
        self.recent_alerts.append(rec)
        _STATS.alerts_emitted += 1
        if transition == "resolved":
            self._resolved_total += 1
        if self.alerts_path is not None:
            if self._alerts_fh is None:
                self._alerts_fh = open(self.alerts_path, "a",
                                       encoding="utf-8")
            self._alerts_fh.write(json.dumps(rec, sort_keys=True)
                                  + "\n")
            self._alerts_fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._alerts_fh is not None:
                self._alerts_fh.close()
                self._alerts_fh = None

    # -- reads ------------------------------------------------------------
    def alert_counts(self) -> Dict:
        with self._lock:
            pending = sum(1 for s in self.alert_states.values()
                          if s.state == "pending")
            firing = [s for s in self.alert_states.values()
                      if s.state == "firing"]
            return {"pending": pending, "firing": len(firing),
                    "page": sum(1 for s in firing
                                if s.severity == "page"),
                    "ticket": sum(1 for s in firing
                                  if s.severity == "ticket")}

    def report(self, now: Optional[float]) -> Dict:
        t = time.monotonic() if now is None else now
        merged = self.merged_sketches()
        with self._lock:
            burns = {}
            for rule in self.rules:
                gl, bl = self.availability.window(rule["long_s"], t)
                gs, bs = self.availability.window(rule["short_s"], t)
                burns[rule["name"]] = {
                    "long": round(_burn(gl, bl,
                                        self.spec.availability), 4),
                    "short": round(_burn(gs, bs,
                                         self.spec.availability), 4)}
            active = [{"alert": s.alert, "rule": s.rule,
                       "severity": s.severity, "replica": s.replica,
                       "state": s.state, "episode": s.episode}
                      for s in sorted(self.alert_states.values(),
                                      key=lambda s: (s.alert, s.rule,
                                                     s.replica))
                      if s.state != "inactive"]
            return {
                "schema": 1,
                "enabled": True,
                "rel_err": self.rel_err,
                "window_scale": self.window_scale,
                "spec": self.spec.to_dict(),
                "segments": {name: sk.snapshot()
                             for name, sk in sorted(merged.items())},
                "availability": {
                    "target": self.spec.availability,
                    "good": self.availability.good,
                    "bad": self.availability.bad,
                    "burn": burns},
                "alerts": dict(self.alert_counts(),
                               emitted=_STATS.alerts_emitted,
                               resolved_total=self._resolved_total,
                               active=active),
                "replicas": sorted(self.peers),
            }


# ---------------------------------------------------------------------------
# Module API
# ---------------------------------------------------------------------------

_ENGINE: Optional[_Engine] = None
_CFG: Dict = {}


def configure(enabled: bool = False, *, rel_err: float = 0.02,
              max_buckets: int = 512, window_scale: float = 1.0,
              spec=None, alerts_path: Optional[str] = None,
              hb_gap_mult: float = 5.0, hb_gap_min_s: float = 1.0,
              clock_mult: float = 3.0, clock_slack_us: float = 1000.0,
              spike_window_s: float = 2.0, spike_mult: float = 8.0,
              anomaly_pending_s: float = 0.1,
              anomaly_resolve_s: float = 0.25) -> None:
    """Arm (or disarm) the online SLO engine.

    ``enabled=True`` builds a FRESH engine — sketches, windows, and
    alert state all start empty (documented reset semantics; bench
    relies on this to separate its clean and chaos arms).  When
    disabled, every feed is a strict no-op and worker heartbeats carry
    no ``slo`` key at all.
    """
    global _ENGINE, _CFG
    old = _ENGINE
    if not enabled:
        _ENGINE = None
        _CFG = {}
        if old is not None:
            old.close()
        return
    _CFG = {"enabled": True, "rel_err": rel_err,
            "max_buckets": max_buckets, "window_scale": window_scale,
            "spec": SLOSpec.from_dict(spec).to_dict(),
            "alerts_path": alerts_path,
            "hb_gap_mult": hb_gap_mult, "hb_gap_min_s": hb_gap_min_s,
            "clock_mult": clock_mult, "clock_slack_us": clock_slack_us,
            "spike_window_s": spike_window_s,
            "spike_mult": spike_mult,
            "anomaly_pending_s": anomaly_pending_s,
            "anomaly_resolve_s": anomaly_resolve_s}
    _ENGINE = _Engine(rel_err=float(rel_err),
                      max_buckets=int(max_buckets),
                      window_scale=float(window_scale),
                      spec=SLOSpec.from_dict(spec),
                      alerts_path=alerts_path,
                      hb_gap_mult=float(hb_gap_mult),
                      hb_gap_min_s=float(hb_gap_min_s),
                      clock_mult=float(clock_mult),
                      clock_slack_us=float(clock_slack_us),
                      spike_window_s=float(spike_window_s),
                      spike_mult=float(spike_mult),
                      anomaly_pending_s=float(anomaly_pending_s),
                      anomaly_resolve_s=float(anomaly_resolve_s))
    if old is not None:
        old.close()


def enabled() -> bool:
    return _ENGINE is not None


def config() -> Dict:
    """The worker-spec form of the current configuration (what a
    router embeds in a worker spec so the whole fleet samples under
    one spec)."""
    return dict(_CFG)


def observe(segment: str, seconds: float, now=None) -> None:
    """Feed one latency sample.  STRICT no-op when disabled: two
    loads and a return, zero allocation (PR 5 discipline — pinned by
    a tracemalloc test)."""
    eng = _ENGINE
    if eng is None:
        return
    eng.observe(segment, seconds, now)


def observe_outcome(ok: bool, now=None) -> None:
    """Feed one availability event (True = served, False = failed or
    refused).  Strict no-op when disabled."""
    eng = _ENGINE
    if eng is None:
        return
    eng.observe_outcome(ok, now)


def note_replica(name: str, *, hb_gap_s=None, clock_offset_us=None,
                 clock_uncertainty_us=None, counters=None,
                 now=None) -> None:
    """Per-replica anomaly feed (router supervisor).  Runs the
    detectors and steps their alert state machines immediately."""
    eng = _ENGINE
    if eng is None:
        return
    eng.note_replica(name, hb_gap_s=hb_gap_s,
                     clock_offset_us=clock_offset_us,
                     clock_uncertainty_us=clock_uncertainty_us,
                     counters=counters, now=now)


def tick(now=None) -> None:
    """Evaluate burn-rate rules and advance alert state machines."""
    eng = _ENGINE
    if eng is None:
        return
    eng.tick(now)


def wire_payload() -> Optional[Dict]:
    """Cumulative sketch payload for heartbeat piggybacking, or None
    when disabled (callers must OMIT the key entirely — byte-absence,
    PR 15 discipline).  Cumulative-replace, not deltas: ingest is
    last-writer-wins per (replica, generation), so heartbeat loss,
    duplication, and reconnect are all harmless."""
    eng = _ENGINE
    if eng is None:
        return None
    return eng.wire_payload()


def ingest_wire(replica: str, payload: Dict, gen: int = 0) -> None:
    """Adopt one worker's cumulative sketch payload (router side)."""
    eng = _ENGINE
    if eng is None:
        return
    eng.ingest_wire(replica, payload, gen)


def alert_counts() -> Optional[Dict]:
    """{"pending", "firing", "page", "ticket"} or None when
    disabled."""
    eng = _ENGINE
    if eng is None:
        return None
    return eng.alert_counts()


def recent_alerts() -> List[Dict]:
    eng = _ENGINE
    if eng is None:
        return []
    with eng._lock:
        return list(eng.recent_alerts)


def report(now=None) -> Optional[Dict]:
    """Fleet-merged SLO report (local sketches + every ingested
    peer), or None when disabled."""
    eng = _ENGINE
    if eng is None:
        return None
    return eng.report(now)
