"""Order statistics of a list of readings, unrounded."""

from __future__ import annotations


def percentile(values, q: float):
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics; None of nothing."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def durations_ms(spans, name: str):
    return [1e3 * e["dur"] for e in spans.get(name, [])]
