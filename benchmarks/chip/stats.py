"""Order statistics for the benchmark, kept apart from the program's.

``percentile`` interpolates linearly between order statistics, as
``numpy.percentile`` does by default (the arithmetic of
``repro.obs.stats.percentile_summary``), so a percentile of a list that
holds misses is the percentile over every request, misses included.
``spread`` is the run-to-run spread used to set bounds: the interquartile
distance of ``statistics.quantiles(values, n=4)`` over the median.
"""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) of ``values``, linear between
    order statistics. Raises on an empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = math.ceil(rank)
    if lo == hi:
        return float(xs[lo])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median, with the quartiles of ``statistics.quantiles``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
