"""Percentile, spread and due-time arithmetic (no numpy: the parent of a
run stays light and the tests pin these on fixed samples)."""

from __future__ import annotations

import math
import statistics


def percentile(values, p: float) -> float:
    """The p-th percentile by the nearest-rank rule on the sorted sample:
    the smallest value with at least p% of the sample at or below it.
    No interpolation, so the number is always one that was measured."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[rank - 1]


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles `statistics.quantiles(values, n=4)` gives
    (the builder's contract for a bound)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def since_due_ms(due: float, t: float) -> float:
    """Open-loop latency: a request is timed from the instant it was DUE,
    not from when a late generator got round to sending it."""
    return (t - due) * 1e3


def window_offsets(unit_gaps, seconds: float) -> list[float]:
    """Arrival offsets inside [0, seconds) from n unit-mean gaps: the
    cumulative gaps, scaled so that the n arrivals and one closing gap
    fill the window exactly.  A permutation of the same gaps gives the
    same count and the same set of gaps in another order."""
    total = 0.0
    cum = []
    for g in unit_gaps:
        cum.append(total)
        total += g
    scale = seconds / total
    return [c * scale for c in cum]
