"""Percentiles of host-clock samples."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def weighted_percentile(pairs: Sequence[tuple], q: float) -> Optional[float]:
    """Percentile of values with integer weights ``[(value, weight), ...]``:
    each value counts ``weight`` times (a batch's gap is one gap of each of
    its sequences). Nearest rank over the expanded sample."""
    pairs = sorted((v, int(w)) for v, w in pairs if w > 0)
    n = sum(w for _, w in pairs)
    if n == 0:
        return None
    rank = max(1, math.ceil(n * q / 100.0))
    seen = 0
    for v, w in pairs:
        seen += w
        if seen >= rank:
            return v
    return pairs[-1][0]
