"""Exact percentiles over all samples, and the rule on how far they reach.

A percentile is reported only where at least five samples lie beyond it:
``percentile`` raises otherwise, so a window too short for its tail fails
loudly instead of reporting a maximum under a percentile's name.  Five, not
more: a forget cell's drains take some tenths of a second each, so a window
of the longest length a check allows holds some tens of forget requests,
and a p90 over 66 of them has 7 beyond it.
"""
from __future__ import annotations

import math
from typing import Sequence

BEYOND = 5


def samples_beyond(n: int, q: float) -> int:
    """Samples strictly above the nearest-rank ``q`` percentile of ``n``."""
    return n - math.ceil(q * n)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``0 < q < 1``) of all ``values``."""
    n = len(values)
    if not 0.0 < q < 1.0:
        raise ValueError(f"percentile q must lie in (0, 1), got {q}")
    if samples_beyond(n, q) < BEYOND:
        raise ValueError(
            f"p{q * 100:g} of {n} samples has {samples_beyond(n, q)} beyond "
            f"it; at least {BEYOND} are needed (lengthen the window or raise "
            f"the rate)")
    return float(sorted(values)[math.ceil(q * n) - 1])


def median(values: Sequence[float]) -> float:
    v = sorted(values)
    n = len(v)
    if n == 0:
        raise ValueError("median of no samples")
    return float(v[n // 2] if n % 2 else 0.5 * (v[n // 2 - 1] + v[n // 2]))
