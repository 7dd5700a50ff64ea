"""Percentiles and spreads, the same rule in every cell and every PR."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0 < q < 100) by linear interpolation between
    order statistics (numpy's default rule). ``None`` for no values.

    Callers put a request that never produced what is measured in the
    sample as a value later than any measured one (see
    :func:`latest_for_missing`), so a stall moves the tail instead of
    leaving the sample."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latest_for_missing(measured: Sequence[float], waited: float) -> float:
    """The value a missing sample takes: later than every measured value
    and than the ``waited`` seconds it was given."""
    return max([waited, *measured]) + 1.0


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, by
    ``statistics.quantiles(values, n=4)`` (the rule the bounds are set
    by)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
