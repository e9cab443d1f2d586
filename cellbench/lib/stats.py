"""Percentile arithmetic (the helper of ``benchmarks/latency_under_load.py``,
copied: nearest rank on the sorted sample)."""

from __future__ import annotations


def percentile(sorted_vals: list[float], p: float) -> float | None:
    if not sorted_vals:
        return None
    k = min(len(sorted_vals) - 1,
            max(0, int(round(p / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[k]
