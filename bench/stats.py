"""End-to-end metric arithmetic over the served requests (host clock).

Input: the served ``Request`` objects (the scheduler's timestamps, on
``serve()``'s clock) and the per-chunk delivery record the harness keeps.
All percentiles are nearest-rank: the p-th percentile of n values is the
ceil(p/100 * n)-th smallest, so it is a value that was measured.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Sequence, Tuple

INF = float("inf")


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile; inf stays inf (a failed request is
    infinitely late). Empty input has no percentile."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return v[max(math.ceil(p / 100.0 * len(v)), 1) - 1]


def median(values: Sequence[float]) -> float:
    return percentile(values, 50)


def due_in(reqs: Iterable, start: float, end: float) -> List:
    return [r for r in reqs if start <= r.arrival < end]


def failed(r) -> bool:
    return r.reject_reason is not None or r.t_first_token is None


def ttft_s(r) -> float:
    return INF if failed(r) else r.t_first_token - r.arrival


def ttft_p95_ms(reqs: Sequence) -> float:
    return 1e3 * percentile([ttft_s(r) for r in reqs], 95)


def itl_p95_ms(reqs: Sequence) -> float:
    """95th percentile over every output token's chunk-granular gap
    (``Request.itl``) of the requests; a failed request's tokens never came,
    so it adds one infinite gap."""
    gaps: List[float] = []
    for r in reqs:
        gaps.extend([INF] if failed(r) else r.itl)
    return 1e3 * percentile(gaps, 95)


def delivered(first_tokens: Iterable[float],
              chunks: Iterable[Tuple[float, int]], start: float,
              end: float) -> int:
    """Output tokens that reached the host inside [start, end): each
    admission's first token at its time, and each decode chunk's tokens at
    the chunk's end."""
    n = sum(1 for t in first_tokens if start <= t < end)
    return n + sum(k for t, k in chunks if start <= t < end)


def met_slo(r, ttft_ms: float, itl_ms: float) -> bool:
    """TTFT within its limit and the request's own p95 gap within the ITL
    limit; a failed request missed both."""
    if failed(r) or 1e3 * ttft_s(r) > ttft_ms:
        return False
    return not r.itl or 1e3 * percentile(r.itl, 95) <= itl_ms


def slo_met_pct(reqs: Sequence, ttft_ms: float, itl_ms: float) -> float:
    return 100.0 * sum(met_slo(r, ttft_ms, itl_ms) for r in reqs) / len(reqs)
