"""Latency arithmetic of the serving cells, on the host's clock.

Times are ``time.perf_counter()`` seconds. A request's tokens are stamped
when the ``step()`` that produced them returned, which is when a client of
the engine could first see them.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (linear interpolation between order
    statistics, numpy's default); raises on an empty sample."""
    if len(values) == 0:
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(due: Sequence[float], first: Sequence[Optional[float]],
          window_end: float) -> List[float]:
    """Time to first token of every request due in the window, from its
    due time. A request with no first token when the window closed counts
    at ``window_end - due``: censored, so a stall shows instead of
    vanishing from the sample."""
    out = []
    for d, f in zip(due, first):
        if d >= window_end:
            continue
        out.append((f if f is not None and f <= window_end else window_end)
                   - d)
    return out


def token_gaps(stamps: Sequence[Sequence[float]], start: float, end: float
               ) -> List[float]:
    """Every gap between consecutive output tokens of each request, both
    tokens stamped inside ``[start, end]``."""
    gaps: List[float] = []
    for s in stamps:
        inside = [t for t in s if start <= t <= end]
        gaps.extend(np.diff(inside).tolist())
    return gaps
