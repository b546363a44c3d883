"""Percentile and spread arithmetic of the benchmark.

``percentile`` is a copy of the linear interpolation between closest ranks
that ``numpy.percentile`` uses by default (and that
``repro.serving.runner._pctl`` calls), written out so that the yardstick
does not move with numpy or with the program. ``spread`` is the
interquartile distance of ``statistics.quantiles(values, n=4)`` as a share
of the median: the measure the benchmark's bounds are set from.
"""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) of ``values``, linear between ranks."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values) -> float:
    """(Q3 - Q1) / median, quartiles as ``statistics.quantiles`` gives them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
