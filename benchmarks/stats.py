"""The percentile rule of the benchmark, in one place.

A timing is reported as a median and as the highest percentile that still
has ten samples beyond it: asked for p99 with fewer than 1,000 samples, the
rule falls to the highest percentile (p95, p90, p75) that does, and says
which one it used. Quantiles are by the nearest-rank method on the sorted
sample, so every reported value is a value that was measured.
"""

from __future__ import annotations

import math

LADDER = (99.9, 99.0, 95.0, 90.0, 75.0, 50.0)
BEYOND = 10


def nearest_rank(sorted_values, pct: float):
    """The value at the pct-th percentile of an ascending sequence."""
    n = len(sorted_values)
    if n == 0:
        raise ValueError("no samples")
    k = max(1, math.ceil(pct / 100.0 * n))
    return sorted_values[min(n, k) - 1]


def supported_percentile(n: int, asked: float) -> float:
    """The highest rung of LADDER, not above `asked`, with at least BEYOND
    samples above it in a sample of n; 50 when even that is not there."""
    for pct in LADDER:
        if pct <= asked and n * (100.0 - pct) / 100.0 >= BEYOND:
            return pct
    return 50.0


def tail(values, asked: float = 99.0) -> tuple[float, float, int]:
    """(value, percentile used, sample count) under the rule above."""
    xs = sorted(values)
    pct = supported_percentile(len(xs), asked)
    return float(nearest_rank(xs, pct)), pct, len(xs)


def median(values) -> float:
    return float(nearest_rank(sorted(values), 50.0))
