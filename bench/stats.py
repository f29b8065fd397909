"""Percentiles, quartile spreads and window arithmetic.

Every number the benchmark reports from host timestamps goes through
these few functions, so a later change to the system cannot change how
a number is computed.
"""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    """Nearest-rank p-th percentile (0 < p <= 100): the smallest value
    with at least p% of the sample at or below it.  A missing value is
    passed as math.inf and sorts last, so a request that never answered
    counts against the tail.  None for an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[k - 1]


def quartile_spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median, with Q1 and Q3 as statistics.quantiles(n=4)
    gives them (its default, exclusive method)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def union_length(intervals: Iterable[Tuple[float, float]],
                 lo: float = -math.inf, hi: float = math.inf) -> float:
    """Total length covered by the (start, end) intervals inside
    [lo, hi], overlaps counted once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The sub-intervals of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]
