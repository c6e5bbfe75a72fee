"""Metric arithmetic shared by the readers, kept apart so that it is
tested once: percentiles over every event (an event that never arrived
counts as slower than any that did) and rates over a whole window.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

__all__ = ["percentile", "rate"]


def percentile(values: Sequence[float], q: float, n_failed: int = 0) -> Optional[float]:
    """The ``q``-th percentile (0-100, nearest rank) over ``values`` plus
    ``n_failed`` events that never completed.  A failed event ranks above
    every completed one, so when the rank lands on one the result is
    ``inf``.  ``None`` when there is no event at all."""
    n = len(values) + int(n_failed)
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))
    ordered = sorted(float(v) for v in values)
    return ordered[rank - 1] if rank <= len(ordered) else math.inf


def rate(work: float, seconds: float) -> Optional[float]:
    """Work per second over the whole window; ``None`` for an empty
    window."""
    return float(work) / seconds if seconds > 0 else None
