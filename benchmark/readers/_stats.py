"""Shared arithmetic for the readers."""

from __future__ import annotations

from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs: List[float] = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def lookup(obj, dotted: str):
    for part in dotted.split("."):
        obj = obj[part]
    return obj
