"""Percentile and latency arithmetic. Exact, over every sample: a run
has hundreds to thousands of them, so no sketch is needed."""
import math


def percentile(samples, q):
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), on a plain list; None when empty."""
    xs = sorted(samples)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    k = (len(xs) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def median(samples):
    return percentile(samples, 50)


def tpot_s(t_first, t_last, n_tokens):
    """One request's time per output token: (last - first) / (n - 1).
    Per request and not per gap, because run-ahead blocks deliver
    tokens in bursts. None for a one-token reply (no gap exists)."""
    if n_tokens < 2:
        return None
    return (t_last - t_first) / (n_tokens - 1)


def spread(samples):
    """Distance between the quartiles over the median (how the driver
    reads run-to-run spread)."""
    m = median(samples)
    if not m:
        return None
    return (percentile(samples, 75) - percentile(samples, 25)) / abs(m)
