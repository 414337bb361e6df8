"""Percentiles with their sample-count rule, and the spread the bounds are
set from. Kept with the benchmark so every PR computes them the same way."""

from __future__ import annotations

import math
import statistics

MIN_BEYOND = 10  # choosing-metrics section 1: ten samples beyond a percentile


class TooFewSamples(ValueError):
    pass


def percentile(values: list[float], q: float,
               min_beyond: int = MIN_BEYOND) -> float:
    """The q-th percentile (0 < q < 100) by the nearest-rank rule, refused
    when fewer than `min_beyond` samples lie beyond it (a p90 needs 100
    samples, a median 20). A failed request is passed in as +inf: it misses
    every percentile it reaches."""
    n = len(values)
    if n == 0:
        raise TooFewSamples("no samples")
    rank = math.ceil(q / 100.0 * n)  # 1-based nearest rank
    beyond = n - rank
    if beyond < min_beyond:
        raise TooFewSamples(
            f"p{q:g} over {n} samples has {beyond} beyond it, "
            f"needs {min_beyond}"
        )
    return sorted(values)[max(rank, 1) - 1]


def spread(values: list[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with Python's `statistics.quantiles(values, n=4)` — the rule the
    bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
