"""Window and percentile arithmetic on plain lists."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; ``inf`` counts as the worst. Raises on an empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or math.isinf(xs[hi]):
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tokens_in_window(token_times, t0: float, t1: float) -> int:
    """How many of the time stamps fall in [t0, t1)."""
    return sum(1 for t in token_times if t0 <= t < t1)


def gaps_in_window(per_request_times, t0: float, t1: float):
    """Every gap between consecutive tokens of one request whose later token
    falls in [t0, t1), all requests pooled."""
    out = []
    for times in per_request_times:
        for a, b in zip(times, times[1:]):
            if t0 <= b < t1:
                out.append(b - a)
    return out
