"""Window arithmetic: rates over the whole window, tails over all
requests."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def rate(work: float, seconds: float) -> float:
    """All the work of the window over all of its time."""
    if seconds <= 0:
        raise ValueError(f"a window of {seconds} s")
    return work / seconds


def tail(latencies: Sequence[Optional[float]], q: float) -> float:
    """Nearest-rank ``q``-th percentile over every request, where a
    request that failed or never answered (None) counts as missing any
    limit: it sorts above every measured latency."""
    if not latencies:
        raise ValueError("no requests")
    xs = sorted(math.inf if x is None else float(x) for x in latencies)
    k = min(len(xs) - 1, max(0, math.ceil(q / 100.0 * len(xs)) - 1))
    return xs[k]
