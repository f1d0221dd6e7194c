"""The arithmetic behind every end-to-end number, in one place.

An end-to-end rate is all the work of the window over all its timed
wall, and a latency statistic is over every block due in the window:
a stall inside the window is in the score.  The steadier statistics
(the median of per-pass rates, the share of slow passes) stand beside
them as per-layer metrics of the harness, never in their place.
"""

from __future__ import annotations

import math
import statistics

# "report the highest percentile that has at least ten samples beyond
# it" (choosing-metrics guide, section 1)
MIN_BEYOND = 10


def median(values) -> float:
    values = list(values)
    if not values:
        raise ValueError("median of no samples")
    return float(statistics.median(values))


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least p% of
    the samples at or below it.  No interpolation, so the number is a
    latency that a block really had."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return float(xs[rank - 1])


def samples_beyond(n: int, p: float) -> int:
    """How many of `n` samples lie beyond the nearest-rank p-th."""
    return n - max(1, math.ceil(p / 100.0 * n))


def percentile_supported(n: int, p: float) -> bool:
    return samples_beyond(n, p) >= MIN_BEYOND


def needed_samples(p: float) -> int:
    """Fewest samples for which the p-th percentile is supported."""
    n = 1
    while not percentile_supported(n, p):
        n += 1
    return n


def _passes(work, walls) -> tuple[list, list]:
    work, walls = list(work), list(walls)
    if len(work) != len(walls):
        raise ValueError("one amount of work per pass wall")
    if not walls or any(w <= 0 for w in walls):
        raise ValueError("a pass wall must be positive, and there must be one")
    return work, walls


def total_rate(work, walls) -> float:
    """All the work of the passes over all their wall: the end-to-end
    rate.  A stalled pass costs what it cost."""
    work, walls = _passes(work, walls)
    return float(sum(work) / sum(walls))


def median_rate(work, walls) -> float:
    """Median over passes of (work of the pass) / (wall of the pass):
    the rate of a typical pass, which sheds the stalls.  Per-layer."""
    work, walls = _passes(work, walls)
    return median(n / w for n, w in zip(work, walls))


def slow_share(walls, factor: float = 1.25) -> float:
    """Share (%) of passes whose wall is over `factor` times the
    median wall of the run."""
    walls = list(walls)
    limit = factor * median(walls)
    return 100.0 * sum(1 for w in walls if w > limit) / len(walls)
