"""Quantiles for the benchmark's tails.

``quantile`` is linear interpolation on sorted samples, numpy's default
method; it is the arithmetic of ``repro.bench.stats.quantile``, copied here
so that the yardstick does not move when the program does.
"""

from __future__ import annotations

from typing import Sequence


def quantile(samples: Sequence[float], q: float) -> float:
    """Linear-interpolated ``q`` quantile of a non-empty sequence."""
    if not samples:
        raise ValueError("quantile of empty sequence")
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile {q} outside [0, 1]")
    xs = sorted(samples)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac
