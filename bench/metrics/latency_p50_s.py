"""Median latency, due time to result read back, over every request due
in the window (a request never completed has none, and fails the run)."""

import math

from bench.stats import percentile


def read(run):
    lat = run.latencies_s
    if not lat:
        return None
    v = percentile(lat, 50)
    return v if math.isfinite(v) else None
