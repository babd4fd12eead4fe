"""Arithmetic of the end-to-end numbers: rates over a window and tails over
all requests."""
from __future__ import annotations

import math

import numpy as np


def rate(count: float, seconds: float) -> float:
    """Work per second over the whole window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds


def percentile(values, q: float) -> float:
    """Nearest-rank percentile ``q`` (0-100] of every value: the smallest
    value with at least q% of the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * v.size))
    return float(v[rank - 1])
