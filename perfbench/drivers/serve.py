"""The serving job: the decode-tier model the configuration's `builder`
names behind `ServingEngine`, driven through `submit_decode` by ONE
thread that both sends and listens.

The thread sweeps the requests in flight about 1,500 times a second
and stamps each token with the sweep's clock when it first sees it, so
a token's time "at the client" is at most one sweep (< 1 ms) late. A
thread per stream on `reply.tokens()` would be the other way to
listen; at thousands of tokens a second its wake-ups contend with the
engine's dispatcher for the interpreter lock and the benchmark would
measure itself. The sweep reads the reply's token list length, which
is the one place the benchmark looks past the public API (PERF.md,
Open questions: a non-blocking count on `ServeReply`).

Open loop: a request is timed from when it was DUE, sent or not.
Closed loop: from just before `submit_decode`. Limits are judged here,
afterwards; no request carries a deadline (a deadline switches the
engine's run-ahead off).

A late answer is late, not failed. The engine sheds a session it has no
slot for with `ServeOverloadError.retry_after_ms`, which its contract
calls "come back in N ms" and not a terminal failure: the client here
comes back, and the request's times still run from when it was due.
After the close an open-loop run waits for every request of the window
that has no answer yet, up to `LATE_S`. So a host that stands still for
some seconds (a burst of the arrivals it held back meets a full slot
pool) shows in the latencies and the rate, which is where it belongs,
and `failed` counts what never got an answer or got an error.
"""
import collections
import time

import numpy as np

from perfbench.harness import cell as cell_mod
from perfbench.harness import compare, numbers, profiler, traffic

UNATTRIBUTED_GAP = "engine-thread"
SWEEP_S = 0.0005
LATE_S = 60.0   # past the close, how long an open-loop answer is waited for


class Rec:
    """One request as the client saw it (times on perf_counter)."""
    __slots__ = ("req", "ids", "reply", "t_due", "t_sent", "t_first",
                 "t_last", "seen", "t_end", "error", "late", "sheds")

    def __init__(self, req, ids, t_due):
        self.req, self.ids, self.t_due = req, ids, t_due
        self.reply = self.t_sent = self.t_first = self.t_last = None
        self.t_end = self.error = self.late = None
        self.seen = self.sheds = 0


class Load:
    """The load generator and listener. `drive(until)` runs it up to a
    time; state carries over, so the window and the traced tail after
    it are one uninterrupted stream of traffic."""

    def __init__(self, run, engine, vocab, horizon_s):
        from singa_tpu import serve

        self.run, self.engine, self.vocab = run, engine, vocab
        self.w = run.workload
        self.overload = serve.ServeOverloadError
        self.closed = self.w["loop"] == "closed"
        self.live, self.ended = [], []
        # shed requests waiting out the engine's hint, oldest first, and
        # when the hint says a slot may be free (one clock for them all:
        # the pool that was full for one is full for the next)
        self.shed, self.t_retry = collections.deque(), 0.0
        self.t_open = None          # perf_counter of the window's opening
        self.tokens_in_window = 0
        self.window = (float("inf"), float("inf"))
        if self.closed:
            self.next_k = [0] * int(self.w["clients"])
        else:
            self.schedule = traffic.open_schedule(self.w, run.seed,
                                                  horizon_s)
            self.next_i = 0

    # -- sending ----------------------------------------------------------
    def _submit(self, req, t_due=None, t_ready=None):
        """Open loop: `t_due` is the schedule's time. Closed loop: the
        request is due now, and `t_ready` is when the engine delivered
        the client's previous reply (None for its first)."""
        rec = Rec(req, traffic.prompt_ids(req, self.vocab), t_due)
        if self.closed:
            rec.t_due = time.perf_counter()
            rec.late = None if t_ready is None else rec.t_due - t_ready
        if not self._send(rec):
            self.shed.append(rec)
        if not self.closed:
            rec.late = rec.t_sent - rec.t_due

    def _send(self, rec):
        """One attempt. True: the engine has the request. False: it shed
        it at admission, and its hint says when to come back."""
        req = rec.req
        with self.run.annotate("submit_decode"):
            try:
                rec.reply = self.engine.submit_decode(
                    rec.ids, req.n_new, temperature=req.temperature,
                    top_k=req.top_k, seed=req.index)
            except self.overload as e:
                rec.sheds += 1
                self.t_retry = (time.perf_counter()
                                + max(float(e.retry_after_ms), 1.0) / 1e3)
            if rec.t_sent is None:
                rec.t_sent = time.perf_counter()
        if rec.reply is not None:
            self.live.append(rec)
        return rec.reply is not None

    def _come_back(self, now):
        """Re-send what was shed, oldest first, once the hinted wait is
        over; stop at the first the engine sheds again."""
        while self.shed and now >= self.t_retry:
            if not self._send(self.shed[0]):
                return
            self.shed.popleft()

    def _client_next(self, client, t_ready=None):
        k = self.next_k[client]
        self.next_k[client] = k + 1
        self._submit(traffic.closed_request(self.w, self.run.seed,
                                            client, k), t_ready=t_ready)

    def start_clients(self):
        for c in range(len(self.next_k)):
            self._client_next(c)

    # -- listening --------------------------------------------------------
    def _sweep(self, now):
        in_window = self.window[0] <= now < self.window[1]
        live, self.live = self.live, []
        for rec in live:
            n = len(rec.reply._stream)
            if n > rec.seen:
                if rec.seen == 0:
                    rec.t_first = now
                rec.t_last = now
                if in_window:
                    self.tokens_in_window += n - rec.seen
                rec.seen = n
            elif rec.reply.done():
                rec.t_end = now
                try:
                    rec.reply.result(timeout=0)
                except Exception as e:  # noqa: BLE001 — the engine's
                    # verdict on this request, whatever it is, is data
                    rec.error = e
                self.ended.append(rec)
                if self.closed:
                    # ready when the engine delivered, on the same clock
                    self._client_next(rec.req.client, rec.reply.t_reply)
                continue
            self.live.append(rec)

    def drive(self, until):
        """Send what is due and listen, until perf_counter `until`."""
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            nap = SWEEP_S
            self._come_back(now)
            if not self.closed:
                while (self.next_i < len(self.schedule) and self.t_open
                       + self.schedule[self.next_i].due_s <= now):
                    req = self.schedule[self.next_i]
                    self.next_i += 1
                    self._submit(req, self.t_open + req.due_s)
                if self.next_i < len(self.schedule):
                    nap = min(nap, max(0.0, self.t_open + self.schedule[
                        self.next_i].due_s - time.perf_counter()))
            self._sweep(time.perf_counter())
            time.sleep(nap)

    def all_streaming(self):
        return not self.shed and all(r.seen > 0 for r in self.live)

    def unsent(self, t0, t1):
        """Open loop: the schedule's requests due in [t0, t1) that were
        never sent (the host stood still up to now), as records."""
        if self.closed:
            return []
        return [Rec(q, None, self.t_open + q.due_s)
                for q in self.schedule[self.next_i:]
                if t0 <= self.t_open + q.due_s < t1]

    def unanswered(self, t0, t1):
        """How many requests due in [t0, t1) have no answer yet: not
        sent, shed and waiting, or in flight."""
        return len(self.unsent(t0, t1)) + sum(
            1 for r in list(self.shed) + self.live if t0 <= r.t_due < t1)


def build(run):
    """The model on the device from the seed, and the engine started
    and warmed for this cell's shapes and no others."""
    from singa_tpu import device, serve, tensor

    sec, w = run.config["serve"], run.workload
    cell_mod.set_policies(sec)
    dev = device.create_tpu_device()
    dev.SetRandSeed(run.seed)
    model = cell_mod.build(run.config["builder"])
    model.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                     device=dev)],
                  is_train=False, use_graph=False)
    model.eval()
    run.mark("model build")
    _, longest, new = traffic.limits(w)
    if longest + new > model.max_len:
        raise ValueError(
            f"traffic reaches {longest} + {new} tokens, beyond the "
            f"model's {model.max_len} positions: an operation would fail")
    # the deployment's engine settings; a cell's traffic file may size
    # the slot pool for its own lengths (as a training cell's names its
    # batch), every other setting stays the configuration's
    settings = {**sec["engine"], **w.get("engine", {})}
    engine = serve.ServingEngine(model, **settings).start()
    t0 = time.perf_counter()
    warmed = engine.warm_decode(prompt_lens=traffic.prompt_buckets(w),
                                max_new_tokens=new,
                                samplers=traffic.samplers(w))
    run.notes["warm_decode"] = (f"{warmed} executables in "
                                f"{time.perf_counter() - t0:.2f} s")
    run.mark("engine start and warm_decode")
    return model, engine


def reference_check(run, model, recs):
    """The first `streams` completed replies against the reference's
    full forward over prompt + reply, padded to one fixed length so the
    check is one executable."""
    chk = run.config["serve"]["check"]
    ref = cell_mod.module("reference", run.config["reference"]["module"])
    kwargs = run.config["reference"].get("kwargs", {})
    recs = recs[:chk["streams"]]
    if len(recs) < chk["streams"]:
        run.wrong.append(f"only {len(recs)} completed stream(s) to check "
                         f"against the reference, need {chk['streams']}")
        return
    _, longest, new = traffic.limits(run.workload)
    states = {k: v.data for k, v in model.get_states().items()}
    worst, ok, stds = 0.0, True, []
    for i in range(0, len(recs), chk["chunk"]):
        part = recs[i:i + chk["chunk"]]
        seqs = np.zeros((len(part), longest + new), np.int32)
        for b, rec in enumerate(part):
            full = np.asarray(rec.reply.result(timeout=0))[0]
            if not np.array_equal(full[:len(rec.ids)], rec.ids):
                run.wrong.append("a reply does not start with its prompt")
                return
            seqs[b, :len(full)] = full
        shortfall, std = ref.served_shortfall(states, seqs, **kwargs)
        good, w_ = compare.served_within_margin(
            np.asarray(shortfall), [len(r.ids) for r in part],
            [len(r.ids) + r.req.n_new for r in part], chk["margin"])
        ok, worst = ok and good, max(worst, w_)
        stds.append(float(std))
    run.notes["reference_check"] = (
        f"{len(recs)} served streams: worst served token trails the "
        f"reference's best logit by {worst:.4f} (margin {chk['margin']}; "
        f"the logits' std is {np.mean(stds):.3f})")
    run.compared["served_token_under_reference_best"] = (
        worst, chk["margin"])
    if not ok:
        run.wrong.append(f"a served token trails the reference's best "
                         f"logit by {worst:.4f} (margin {chk['margin']})")


def _decode_counters():
    from singa_tpu import stats

    return dict(stats.cache_stats()["decode"])


def run(run):
    from singa_tpu import device, trace

    w = run.workload
    seconds, grace = run.seconds, float(w.get("grace_s", 0.0))
    tail = float(w["trace_seconds"]) if run.trace else 0.0
    model, engine = build(run)
    try:
        # arrivals go on through the wait for late answers and the
        # traced tail: one uninterrupted stream, however long the wait
        load = Load(run, engine, model.vocab_size,
                    seconds + max(grace, LATE_S) + tail + 1.0)
        if run.trace:
            device.set_tracing(True, ring_capacity=4_000_000)
        if load.closed:
            # steady state before the window: every client streaming
            load.start_clients()
            limit = time.perf_counter() + 120.0
            while not load.all_streaming():
                if time.perf_counter() > limit:
                    raise RuntimeError("the slots did not fill in 120 s")
                load.drive(time.perf_counter() + 0.02)
            load.t_open = t_open = time.perf_counter()
        else:
            load.t_open = t_open = (time.perf_counter()
                                    + float(w.get("lead_s", 0.0)))
            load.drive(t_open)
        run.mark("slot fill or lead-in", t_open)
        load.window = (t_open, t_open + seconds)
        trace.clear()
        before = _decode_counters()
        compiles0 = run.meter.compiles
        run.end_to_end["setup_s"] = t_open - run.t_process_start

        load.drive(t_open + seconds)
        after = _decode_counters()
        run.compiles_in_window = run.meter.compiles - compiles0
        if run.trace:
            run.spans = trace.records()
        load.drive(t_open + seconds + grace)   # open loop: stragglers
        while (not load.closed and load.unanswered(t_open, t_open + seconds)
               and time.perf_counter() < t_open + seconds + LATE_S):
            load.drive(time.perf_counter() + 0.05)     # late, not failed
        t_judged = time.perf_counter()
        if run.trace:
            with profiler.DeviceTrace(run):
                load.drive(time.perf_counter() + tail)
    finally:
        engine.stop(drain=False)

    run.counters["decode"] = {k: after[k] - before[k] for k in after
                              if isinstance(after[k], (int, float))}
    finished = summarize(run, load, t_open, t_judged)
    total = _decode_counters()
    if total["sessions"] != (total["completed"] + total["failed"]
                             + total["expired"] + total["shed"]):
        run.wrong.append(f"decode counters do not reconcile: {total}")
    reference_check(run, model, finished)


def summarize(run, load, t_open, t_judged):
    """The end-to-end numbers, from the client's stamps alone. Returns
    the requests that finished inside the window, in order."""
    seconds = run.seconds
    t_close = t_open + seconds
    everything = (load.ended + load.live + list(load.shed)
                  + load.unsent(t_open, t_close))
    if load.closed:
        asked = [r for r in everything if t_open <= r.t_due < t_close]
        judged = [r for r in load.ended if t_open <= r.t_end < t_close]
        waiting = sum(1 for r in asked if r.t_first is None
                      and r.error is None)
        asked = [r for r in asked if r.t_first is not None or r.error]
    else:
        asked = judged = [r for r in everything
                          if t_open <= r.t_due < t_close]
        waiting = 0
    # a request that failed, was shed, or has no first token when the
    # judging ends counts as the worst: the whole time it was given
    ttft = [min(r.t_first if r.t_first is not None else t_judged, t_judged)
            - r.t_due for r in asked]
    finished = [r for r in load.ended if r.error is None
                and t_open <= r.t_end < t_close]
    tpot = [v for v in (numbers.tpot_s(r.t_first, r.t_last, r.seen)
                        for r in finished) if v is not None]
    failed = [r for r in judged if r.error is not None or r.t_end is None
              or r.t_end > t_judged]
    run.attempted, run.failed = len(judged), len(failed)
    if not ttft or not tpot:
        run.wrong.append(f"nothing to judge: {len(ttft)} first tokens and "
                         f"{len(tpot)} finished requests in the window")
        return finished
    run.end_to_end["out_tokens_per_s"] = load.tokens_in_window / seconds
    run.end_to_end["ttft_p90_ms"] = 1e3 * numbers.percentile(ttft, 90)
    run.end_to_end["tpot_p50_ms"] = 1e3 * numbers.median(tpot)
    run.samples["ttft_s"], run.samples["tpot_s"] = ttft, tpot
    run.samples["gen_late_s"] = [
        r.late for r in everything
        if r.late is not None and t_open <= r.t_due < t_close]
    errors = sorted({type(r.error).__name__ for r in failed if r.error})
    came_back = [r for r in judged if r.sheds]
    run.notes["serve"] = (
        f"{load.tokens_in_window} tokens in {seconds:.1f} s; ttft p50 "
        f"{1e3 * numbers.median(ttft):.2f} ms p90 "
        f"{1e3 * numbers.percentile(ttft, 90):.2f} ms over {len(ttft)} "
        f"requests; tpot p50 {1e3 * numbers.median(tpot):.3f} ms p90 "
        f"{1e3 * numbers.percentile(tpot, 90):.3f} ms over {len(tpot)} "
        f"finished; attempted {run.attempted} failed {run.failed} {errors}; "
        f"{len(came_back)} shed at admission and sent again "
        f"({sum(r.sheds for r in came_back)} times), judged "
        f"{t_judged - t_close:.2f} s after the close; "
        f"{waiting} without a first token at the close left out; in flight "
        f"at the close {len(load.live)}")
    return finished
