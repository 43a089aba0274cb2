"""Percentiles for the benchmark's latency metrics.

The same linear interpolation between the two closest ranks as numpy's
default, in the standard library, so that the yardstick does not move with
the program's own statistics helpers.
"""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0 <= q <= 100) of a non-empty sequence."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of empty sequence")
    idx = q / 100.0 * (len(xs) - 1)
    lo = int(idx)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (idx - lo)
