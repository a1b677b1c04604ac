"""Exact order statistics over raw samples (no buckets)."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks, over the raw samples.  ``inf`` samples (failed or
    unfinished requests) sort last and can be the answer: a tail that
    reaches into them IS infinite.  Empty input is an error, not 0."""
    if not samples:
        raise ValueError("percentile of no samples")
    xs = sorted(samples)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if xs[lo] == xs[hi] or math.isinf(xs[lo]):
        return float(xs[lo])
    if math.isinf(xs[hi]):
        return float(xs[hi]) if pos > lo else float(xs[lo])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(samples: Sequence[float]) -> float:
    return percentile(samples, 50.0)
