"""The one general traffic generator: a workload file's parameters and
a seed in, inputs out. Everything is a pure function of (file, seed),
so the same seed gives the same inputs on both sides of a comparison.

A workload file (perfbench/workloads/<cell>.json) is data. Serving:

  loop        "closed" (clients, each sends its next request when the
              last one completes) or "open" (a schedule, sent whether
              or not earlier requests finished)
  clients     closed loop: how many
  arrivals    open loop: {"rate_per_s": r} Poisson at a fixed rate,
              conditioned on its count in every block of "block_s"
              (default 5 s; see `open_schedule`); optional "bursts":
              {"period_s", "on_s", "factor"} raises the rate to
              factor*r for on_s of every period_s and lowers it in
              between so the mean stays r
  lead_s      open loop: arrivals start this long before the window
  classes     [{"weight", "prompt_len", "output_len", "temperature",
              "top_k"}]: a request draws its class by weight; or the
              single-class shorthand: prompt_len / output_len at top
              level. A length is {"dist": "lognormal", "median",
              "sigma", "min", "max"} or {"dist": "fixed", "value"}
  first_output_scale  closed loop: "uniform" multiplies each client's
              FIRST output length by a uniform draw, so the run starts
              in steady state and not with every slot at token 0

Training: "batch", "pool" and "inputs": [{"shape", "dtype", "dist":
"normal" | "randint", "high"}] — a pool of seeded batches.
"""
import dataclasses
import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Request:
    index: int           # open loop: position in the schedule;
    #                      closed loop: k-th request of its client
    client: int          # closed loop: which client; open loop: -1
    due_s: float         # open loop: seconds from window open; else 0
    prompt_len: int
    n_new: int
    temperature: float
    top_k: int
    ids_seed: tuple      # seeds the prompt's token ids


def _rng(*key):
    return np.random.default_rng([int(k) for k in key])


def draw_length(spec, rng):
    if spec["dist"] == "fixed":
        return int(spec["value"])
    if spec["dist"] == "lognormal":
        v = rng.lognormal(math.log(spec["median"]), spec["sigma"])
        return int(min(max(round(v), spec["min"]), spec["max"]))
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _length_bounds(spec):
    if spec["dist"] == "fixed":
        return int(spec["value"]), int(spec["value"])
    return int(spec["min"]), int(spec["max"])


def classes(w):
    if "classes" in w:
        return w["classes"]
    return [{"weight": 1.0, "prompt_len": w["prompt_len"],
             "output_len": w["output_len"]}]


def _draw_request(w, rng, index, client, due_s, ids_seed):
    cs = classes(w)
    weights = np.asarray([c.get("weight", 1.0) for c in cs], float)
    c = cs[int(rng.choice(len(cs), p=weights / weights.sum()))]
    return Request(index, client, due_s,
                   draw_length(c["prompt_len"], rng),
                   draw_length(c["output_len"], rng),
                   float(c.get("temperature", 0.0)),
                   int(c.get("top_k", 0)), ids_seed)


def prompt_ids(req, vocab):
    """Uniform random token ids [prompt_len] int32 for one request."""
    return _rng(*req.ids_seed).integers(
        0, vocab, req.prompt_len, dtype=np.int32)


def limits(w):
    """(smallest prompt, longest prompt, most new tokens) over the
    classes: what warm-up and the slab have to cover."""
    lo = min(_length_bounds(c["prompt_len"])[0] for c in classes(w))
    hi = max(_length_bounds(c["prompt_len"])[1] for c in classes(w))
    new = max(_length_bounds(c["output_len"])[1] for c in classes(w))
    return lo, hi, new


def prompt_buckets(w):
    """One prompt length per power-of-two rung the traffic can reach
    (the engine pads a prompt to its rung, so these are the prefill
    shapes this traffic uses and no others)."""
    lo, hi, _ = limits(w)
    out, b = [], 1
    while b < lo:
        b <<= 1
    while b < hi:
        out.append(b)
        b <<= 1
    return out + [hi]


def samplers(w):
    return sorted({(float(c.get("temperature", 0.0)), int(c.get("top_k", 0)))
                   for c in classes(w)} - {(0.0, 0)})


def _rate_at(arr, t):
    b = arr.get("bursts")
    r = float(arr["rate_per_s"])
    if not b:
        return r
    period, on, f = float(b["period_s"]), float(b["on_s"]), float(b["factor"])
    off = r * (period - f * on) / (period - on)
    if off < 0:
        raise ValueError("bursts: factor * on_s exceeds period_s")
    return f * r if (t % period) < on else off


def _block_times(arr, t0, length, rng):
    """Arrival times in [t0, t0 + length): a Poisson process conditioned
    on its count. The block holds exactly rate * length arrivals at
    independent uniform times (warped by the bursts' intensity where
    there are any)."""
    n = int(round(float(arr["rate_per_s"]) * length))
    u = np.sort(rng.random(n))
    if "bursts" not in arr:
        return t0 + u * length
    grid = np.linspace(0.0, length, int(length * 1000) + 1)
    cum = np.concatenate([[0.0], np.cumsum(
        [_rate_at(arr, t0 + g) for g in grid[:-1]])])
    return t0 + np.interp(u, cum / cum[-1], grid)


def _block_lengths(spec, n, rng):
    """n lengths that are the n quantiles of the distribution, in a
    drawn order: every block carries the same work, whatever the seed."""
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    normal = NormalDist()
    z = [normal.inv_cdf((i + 0.5) / n) for i in range(n)]
    xs = [int(min(max(round(math.exp(math.log(spec["median"])
                                     + spec["sigma"] * v)),
                      spec["min"]), spec["max"])) for v in z]
    return [xs[i] for i in rng.permutation(n)]


def open_schedule(w, seed, seconds):
    """Every request of an open-loop run, in order of `due_s`, from
    `lead_s` before the window opens to `seconds` after.

    Steady by a fixed amount of work drawn from the seed: time is cut
    into blocks of `block_s` (default 5 s, the lead-in one block of its
    own), and every block holds exactly its share of arrivals, of each
    class, and of each length's quantiles. Within a block arrivals are
    Poisson (independent uniform times), so queues build as they do
    under independent users; between runs the offered load is the same
    to the request, and only order, pairing and timing are drawn."""
    arr = w["arrivals"]
    rng = _rng(seed, 1)
    block = float(arr.get("block_s", 5.0))
    lead = float(w.get("lead_s", 0.0))
    edges = ([-lead] if lead else []) + list(np.arange(0.0, seconds, block))
    cs = classes(w)
    weights = np.asarray([c.get("weight", 1.0) for c in cs], float)
    out = []
    for t0, t1 in zip(edges, edges[1:] + [edges[-1] + block]):
        times = _block_times(arr, t0, t1 - t0, rng)
        # each class its share of the block, to the nearest request
        cut = np.round(np.cumsum(weights / weights.sum()) * len(times))
        of = np.searchsorted(cut, np.arange(len(times)), side="right")
        of = of[rng.permutation(len(times))]
        draws = {}
        for ci, c in enumerate(cs):
            k = int(np.sum(of == ci))
            draws[ci] = list(zip(_block_lengths(c["prompt_len"], k, rng),
                                 _block_lengths(c["output_len"], k, rng)))
        for t, ci in zip(times, of):
            if t >= seconds:
                break
            p, n = draws[ci].pop()
            i = len(out)
            out.append(Request(i, -1, float(t), p, n,
                               float(cs[ci].get("temperature", 0.0)),
                               int(cs[ci].get("top_k", 0)), (seed, 2, i)))
    return out


def closed_request(w, seed, client, k):
    """The k-th request of one closed-loop client."""
    rng = _rng(seed, 3, client, k)
    req = _draw_request(w, rng, k, client, 0.0, (seed, 4, client, k))
    if k == 0 and w.get("first_output_scale") == "uniform":
        n = max(1, int(round(req.n_new * rng.random())))
        req = dataclasses.replace(req, n_new=n)
    return req


def batches(w, seed):
    """The training pool: `pool` batches, each a list of host arrays,
    one per entry of `inputs`, with leading dimension `batch`."""
    out = []
    for b in range(int(w["pool"])):
        rng = _rng(seed, 5, b)
        arrays = []
        for spec in w["inputs"]:
            shape = (int(w["batch"]), *spec["shape"])
            if spec["dist"] == "normal":
                a = rng.standard_normal(shape, dtype=np.float32)
            elif spec["dist"] == "randint":
                a = rng.integers(0, int(spec["high"]), shape)
            else:
                raise ValueError(f"unknown input dist {spec['dist']!r}")
            arrays.append(a.astype(spec["dtype"]))
        out.append(arrays)
    return out
