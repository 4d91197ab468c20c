"""Latency arithmetic over the harness's request records.

Times are milliseconds from the window's start.  Time to first token is
from the moment a request was due to its first token.  Time per output token
is (last token - first token) / (tokens - 1) for each request with two or
more tokens; an escalated request's first token is its wave's return.
A percentile is the inclusive linear interpolation between order
statistics (``statistics.quantiles(..., method="inclusive")``).
"""
from __future__ import annotations

import statistics
from typing import List, Optional, Sequence


def percentile(xs: Sequence[float], q: int) -> Optional[float]:
    """The ``q``-th percentile (1..99); None for fewer than two values."""
    xs = [float(x) for x in xs]
    if len(xs) < 2:
        return xs[0] if xs else None
    return statistics.quantiles(xs, n=100, method="inclusive")[q - 1]


def completed(records) -> List[dict]:
    """The requests that came back with their tokens."""
    return [r for r in records if r.get("retire_ms") is not None
            and r["tokens"] is not None]


def ttfts(records) -> List[float]:
    """Per finished request, its due time to its first token."""
    return [r["first_ms"] - r["due_ms"] for r in completed(records)]


def tpots(records) -> List[float]:
    return [(r["retire_ms"] - r["first_ms"]) / (len(r["tokens"]) - 1)
            for r in completed(records) if len(r["tokens"]) > 1]
