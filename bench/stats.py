"""Statistics the metrics share."""
from __future__ import annotations

import math
from typing import List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    v = sorted(values)
    return v[max(1, math.ceil(q / 100.0 * len(v))) - 1]


def p90(values: Sequence[float]) -> float:
    return percentile(values, 90)


def tpot(stamps: List[float]) -> float:
    """Time per output token after the first: (last - first) / (n - 1)."""
    if len(stamps) < 2:
        raise ValueError("time per output token needs two tokens")
    return (stamps[-1] - stamps[0]) / (len(stamps) - 1)
