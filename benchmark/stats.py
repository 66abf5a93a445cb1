"""The arithmetic every metric goes through, kept in one place."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default rule), written out so that the yardstick
    does not change with a library."""
    if not values:
        raise ValueError("percentile of no samples")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def chunk_rate(chunk_seconds: Sequence[float], units_per_chunk: float) -> float:
    """Units per second from the MEDIAN chunk: a few slow chunks cannot
    move it, so it is a per-layer number (`step_ms_p50`, `mfu`) and never
    the end-to-end rate; what it hides shows in `stall_share`."""
    return units_per_chunk / statistics.median(chunk_seconds)


def rate(units: float, seconds: float) -> float:
    """Units per second over ALL of a window: every unit of work done in
    it over all of its wall. What an end-to-end rate is."""
    if seconds <= 0:
        raise ValueError("a rate over no time")
    return units / seconds


def stall_share(chunk_seconds: Sequence[float]) -> float:
    """1 - wall rate / median-chunk rate, in percent: the share of the kept
    wall that the median chunk does not explain. Negative when the few
    chunks off the median were faster than it."""
    med = statistics.median(chunk_seconds)
    return 100.0 * (1.0 - med * len(chunk_seconds) / sum(chunk_seconds))


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, by statistics.quantiles(n=4):
    the spread the driver judges a bound against."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))
