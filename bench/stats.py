"""Percentile arithmetic (copied from the program's
`repro.obs.serve.percentile_summary`, so that the yardstick stays fixed
when the program changes)."""

from __future__ import annotations

import math
from typing import Sequence

import numpy as np


def percentile(samples: Sequence[float], q: float) -> float:
    """The q-th percentile (numpy's linear interpolation); +inf samples
    stand for requests that never completed and sort last."""
    if not len(samples):
        raise ValueError("percentile of no samples")
    a = np.sort(np.asarray(samples, np.float64))
    pos = q / 100.0 * (len(a) - 1)
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if not np.isfinite(a[hi]):
        return math.inf
    return float(a[lo] + (a[hi] - a[lo]) * (pos - lo))


def percentile_summary(samples: Sequence[float]) -> dict:
    """{count, mean, p50, p95, p99, max} of a sample list (empty: zeros)."""
    if not len(samples):
        return {"count": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0,
                "p99": 0.0, "max": 0.0}
    a = np.asarray(samples, dtype=np.float64)
    return {"count": int(a.size), "mean": float(a.mean()),
            "p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)),
            "p99": float(np.percentile(a, 99)), "max": float(a.max())}

