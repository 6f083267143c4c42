"""Arithmetic over raw samples, kept with the benchmark.

Percentiles are numpy's linear interpolation over every raw sample (the
arithmetic ``launch/serve.py`` uses for its summary), never a histogram
bucket.  The spread of a set of runs is the distance between the first and
third quartiles of ``statistics.quantiles(values, n=4)`` over the median.
"""
from __future__ import annotations

import statistics
from typing import Sequence

import numpy as np


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) of the raw samples."""
    a = np.asarray(samples, np.float64)
    if a.size == 0:
        raise ValueError("no samples")
    return float(np.percentile(a, q))


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median."""
    q1, med, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / med if med else float("inf")
