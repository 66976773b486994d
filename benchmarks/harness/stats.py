"""The few statistics the benchmark reports, in one place."""

import math
import statistics


def median(values):
    return float(statistics.median(values))


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    of the samples at or below it. No interpolation, so with few samples
    it is a sample that was measured, never a value beyond the largest."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(q * len(ordered)))
    return float(ordered[rank - 1])

