"""The arithmetic of the end-to-end metrics."""
from __future__ import annotations

from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """q-th percentile (0..100), linear between closest ranks (numpy's
    default).  Raises on an empty sample: a tail of nothing is no reading."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(float(v) for v in values)
    rank = q / 100.0 * (len(xs) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def mean(values: Sequence[float]) -> float:
    if not values:
        raise ValueError("mean of an empty sample")
    return sum(float(v) for v in values) / len(values)


def ratio(numerator: float, denominator: float) -> float:
    if denominator <= 0:
        raise ValueError(f"ratio over a non-positive base {denominator}")
    return float(numerator) / float(denominator)
