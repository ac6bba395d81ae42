"""The arithmetic behind the end-to-end metrics: no chunks, no trimming."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """q-th percentile (0..100) by linear interpolation between closest
    ranks, over ALL the values given."""
    if not values:
        raise ValueError("percentile of nothing")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def rate(n_done: int, window_s: float) -> float:
    """Work completed in the window over the WHOLE window."""
    if window_s <= 0:
        raise ValueError("window must be positive")
    return n_done / window_s


def spread(values) -> float:
    """Interquartile distance as a share of the median (the contract's)."""
    import statistics
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
