"""Percentile arithmetic of the benchmark."""

import math


def percentile(values, q):
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default), over ALL the values given."""
    v = sorted(values)
    if not v:
        return None
    if len(v) == 1:
        return float(v[0])
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def median(values):
    return percentile(values, 50.0)

