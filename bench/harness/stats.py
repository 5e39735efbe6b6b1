"""Percentiles and rates over all arrivals of a window."""
from __future__ import annotations

import math
from typing import Iterable, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``q`` in (0, 100]). A missing result is
    passed in as ``math.inf`` and counts as a miss: it sorts last. None
    when there are no values."""
    xs = sorted(values)
    if not xs:
        return None
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def rate(count: int, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"window must be > 0 s, got {seconds}")
    return count / seconds
