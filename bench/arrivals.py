"""Poisson arrivals, copied from ``repro.serving.arrivals.poisson``.

A copy, so that the offered load of a cell cannot move when the program's
generator changes. Times are integer ticks (0.1 us) of simulated time.
"""
from __future__ import annotations

import numpy as np


def poisson(rate: float, horizon: int, seed: int) -> np.ndarray:
    """Sorted int64 arrival ticks in ``[0, horizon)`` at ``rate`` per tick.

    Cumulative exponential gaps (float64) floored to whole ticks; same-tick
    arrivals are legal.
    """
    if rate <= 0:
        raise ValueError(f"rate must be positive, got {rate}")
    rng = np.random.default_rng(seed)
    n_draw = int(rate * horizon * 1.25) + 64
    t = np.cumsum(rng.exponential(1.0 / rate, size=n_draw))
    while t.size and t[-1] < horizon:
        extra = rng.exponential(1.0 / rate, size=n_draw)
        t = np.concatenate([t, t[-1] + np.cumsum(extra)])
    t = np.sort(np.floor(t).astype(np.int64))
    return t[(t >= 0) & (t < horizon)]
