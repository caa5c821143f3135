"""Percentiles and the failed-request rule. Pure Python, no JAX."""

from __future__ import annotations

import math


def percentile(values, q: float):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule); None on an empty list."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def with_failures(samples, n_failed: int, worst=None):
    """The samples of a window with each failed or undrained request
    counted as the worst sample: ``worst`` when given (the time the
    harness waited for it), else the largest sample seen."""
    xs = [float(v) for v in samples]
    if n_failed:
        w = worst if worst is not None else (max(xs) if xs else float("inf"))
        xs += [float(w)] * n_failed
    return xs

