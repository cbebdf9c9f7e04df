"""Metric arithmetic of the benchmark: percentiles over all requests due in a
window, with the missing-request rule, and the spread the bounds are set from.
Pure Python, no program code."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float, missing: int = 0) -> float:
    """Nearest-rank percentile ``q`` (0 < q <= 1) of ``values`` plus
    ``missing`` requests that never got an answer.  The missing ones sit at
    the top of the distribution: if the rank falls among them the percentile
    is ``inf`` — a tail that was not met is not a short tail."""
    n = len(values) + missing
    if n == 0:
        raise ValueError("percentile of no requests")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"q must be in (0, 1], got {q}")
    rank = max(1, math.ceil(q * n))          # 1-based nearest rank
    if rank > len(values):
        return math.inf
    return sorted(values)[rank - 1]


PERCENTILES = (50, 75, 90, 95, 99)


def window_summary(latencies_ms: Sequence[float], missing: int,
                   acked_in_window: int, window_s: float) -> dict:
    """The window's end-to-end numbers: the rate, and the commit latency at
    the median, the upper quartile and three points of the tail (which of
    them a PR is held to is BENCHMARK.json's choice).  ``latencies_ms``
    holds one entry per request that was due in the window and got its
    acknowledgement (however late); ``missing`` those that failed or never
    did; ``acked_in_window`` the acknowledgements that arrived before the
    window closed."""
    out = {"commits_per_s": acked_in_window / window_s}
    for q in PERCENTILES:
        out[f"commit_p{q}_ms"] = percentile(latencies_ms, q / 100, missing)
    return out


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``), the number a bound is
    set from.  None with fewer than two values or a zero median."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return None if med == 0 else (q3 - q1) / abs(med)
