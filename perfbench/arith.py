"""Arithmetic on what a run observed.  No JAX, no program code."""

from __future__ import annotations

import statistics
from typing import Iterable, List, Sequence, Tuple

#: (time, stream, tokens delivered at that time)
Delivery = Tuple[float, int, int]


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the closest ranks."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(deliveries: Iterable[Delivery], t0: float, t1: float
              ) -> List[Delivery]:
    return [d for d in deliveries if t0 < d[0] <= t1]


def delivered_tokens(deliveries: Iterable[Delivery]) -> int:
    return sum(n for _, _, n in deliveries)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
