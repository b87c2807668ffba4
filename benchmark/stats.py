"""The arithmetic that turns readings into reported numbers."""

from __future__ import annotations

import math


def percentile(values, q):
    """Linear-interpolated percentile of a non-empty list (q in 0..100)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n, q):
    """How many of ``n`` samples lie beyond the q-th percentile."""
    return int(n * (100.0 - q) / 100.0)
