"""Percentile and spread arithmetic of the benchmark (its own copy)."""
from __future__ import annotations

import statistics

import numpy as np


def percentile(samples, q: float) -> float:
    """Linear-interpolated percentile (numpy's default); 0 when empty."""
    a = np.asarray(list(samples), np.float64)
    return float(np.percentile(a, q)) if a.size else 0.0


def spread(values) -> float:
    """Interquartile distance over the median, as
    ``statistics.quantiles(values, n=4)`` places the quartiles."""
    q1, med, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / med if med else float("inf")
