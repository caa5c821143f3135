"""Interval arithmetic on (start, end) pairs: the reduction from device
events to busy time and idle gaps. Copied from the arithmetic of
``triton_dist_tpu/obs/devprof.py`` (``_union``, ``_clip``) so that no later
PR to the program can change how the benchmark reads a trace."""

from __future__ import annotations


def union(ivs) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(ivs):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def union_len(ivs) -> float:
    return sum(b - a for a, b in union(ivs))


def clip(ivs, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals cut to ``[lo, hi]``; those outside vanish."""
    return [(max(a, lo), min(b, hi)) for a, b in ivs
            if b > lo and a < hi]


def gaps(ivs, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]``: what the union leaves out."""
    out, t = [], lo
    for a, b in union(clip(ivs, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(min(a1, b1) - max(a0, b0), 0.0)
