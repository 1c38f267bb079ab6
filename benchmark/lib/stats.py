"""Percentiles and spread: the arithmetic every metric goes through.

One definition, kept with the benchmark, so that two PRs never compute
a median or a tail in two ways.
"""
from __future__ import annotations

from typing import Iterable, Optional, Sequence


def percentile(samples: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between
    the two nearest order statistics; None for no samples."""
    s = sorted(samples)
    if not s:
        return None
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(samples: Iterable[float]) -> Optional[float]:
    return percentile(samples, 50)


def beyond(samples: Sequence[float], q: float) -> int:
    """How many samples lie strictly beyond the q-th percentile: a
    tail is worth reporting with at least ten."""
    p = percentile(samples, q)
    return 0 if p is None else sum(1 for x in samples if x > p)


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median — the run-to-run
    spread the bounds are set from."""
    med = median(values)
    if not med:
        return None
    return (percentile(values, 75) - percentile(values, 25)) / abs(med)


def histogram_quantile(buckets: Sequence[float],
                       cumulative: Sequence[int], count: int,
                       q: float) -> Optional[float]:
    """The q-quantile (0..1) of a cumulative-bucket histogram delta by
    linear interpolation inside the bucket — the estimate
    libs/metrics.Histogram.quantile gives, over a window's delta.
    Observations past the last bound clamp to it."""
    if count <= 0:
        return None
    rank = q * count
    prev_bound, prev_cum = 0.0, 0
    for bound, cum in zip(buckets, cumulative):
        if cum >= rank:
            width = cum - prev_cum
            if width <= 0:
                return bound
            return prev_bound + (bound - prev_bound) * \
                (rank - prev_cum) / width
        prev_bound, prev_cum = bound, cum
    return buckets[-1] if buckets else None
