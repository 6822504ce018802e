"""Order statistics the benchmark reports.

``percentile`` is the program's nearest-rank rule (``core/workload.py``),
copied so that a tail is always computed the same way: deterministic and
free of interpolation.
"""
from __future__ import annotations

import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]); nan on empty input.  An
    infinite value (a request that never finished) sorts last."""
    if not values:
        return float("nan")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile must be in [0, 100], got {q}")
    s = sorted(values)
    rank = max(1, -(-len(s) * q // 100))  # ceil without floats
    return float(s[int(rank) - 1])


def median(values: Sequence[float]) -> float:
    return float(statistics.median(values)) if values else float("nan")
