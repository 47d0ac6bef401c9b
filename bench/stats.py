"""Statistics of a run's samples, kept with the benchmark."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the closest ranks (numpy's default, ``statistics``' inclusive
    method)."""
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    s = sorted(xs)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def drained_rate(n_sent: int, t_open: float,
                 completions: Sequence[float]) -> Optional[float]:
    """Requests per second over the time from the window's opening to
    the last completion of the requests sent while it was open."""
    if n_sent == 0 or not completions:
        return None
    span = max(completions) - t_open
    if span <= 0:
        raise ValueError("the last completion precedes the window")
    return n_sent / span

