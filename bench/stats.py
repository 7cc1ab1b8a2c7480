"""End-to-end statistics of one run: percentiles and token gaps.

Pure Python and NumPy, so the tests can check them without a device.
"""
from __future__ import annotations

import math
from typing import Iterable, Sequence

INF = math.inf


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-quantile (0 < q <= 1): the smallest value with at
    least ``q`` of the samples at or below it.  Infinite values (requests
    that failed or never finished) sort last and can be the answer."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"quantile {q} outside (0, 1]")
    ordered = sorted(values)
    rank = max(math.ceil(q * len(ordered)), 1)
    return ordered[rank - 1]


def ttfts(requests: Iterable, window: tuple[float, float]) -> list[float]:
    """Time to first token, due time to the host receiving the first token,
    of every request *due* inside ``window``; a request that failed or has
    no token is infinitely late.  Times are on one clock (``due_at`` and
    ``token_times`` of each request)."""
    lo, hi = window
    out = []
    for r in requests:
        if not lo <= r.due_at < hi:
            continue
        if r.failed or not r.token_times:
            out.append(INF)
        else:
            out.append(r.token_times[0] - r.due_at)
    return out


def itl_gaps(requests: Iterable, window: tuple[float, float]) -> list[float]:
    """Every gap between consecutive output tokens of one request whose two
    tokens both reached the host inside ``window``."""
    lo, hi = window
    gaps = []
    for r in requests:
        t = r.token_times
        for a, b in zip(t, t[1:]):
            if lo <= a and b <= hi:
                gaps.append(b - a)
    return gaps

