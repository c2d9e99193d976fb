"""Interval arithmetic shared by the runtime's accounts and the trace
analyzer (stdlib only: ``obs/report.py`` must stay importable without
JAX)."""

from __future__ import annotations


def union_seconds(
    intervals: list[tuple[float, float]],
    lo: float = float("-inf"),
    hi: float = float("inf"),
) -> float:
    """Seconds of ``[lo, hi]`` covered by the union of possibly-overlapping
    ``(start, end)`` intervals."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total
