"""The program's span and counter digest over the traced window, for the
per-layer metrics that read it (`bench/metrics/`).

While a JAX profiler trace is live, the program's spans and counters add
to `repro.obs.trace.digest()`: per span name its count and total, self
and longest seconds, per counter its sum.  The harness runs the profiler
over the window alone (`--trace 1`), so the digest covers exactly the
window's program calls.  A program that keeps no digest, or a run that
traced nothing, gives `None`, and every reader of it then reads nothing.
"""

from __future__ import annotations

from typing import Optional


def read() -> Optional[dict]:
    try:
        from repro.obs.trace import digest
    except ImportError:
        return None
    d = digest()
    return d if d["spans"] or d["counters"] else None


def span_ms_per_call(name: str, part: str = "total_s") -> Optional[float]:
    """Milliseconds of span `name` per call (`part`: its total or its
    self seconds), or None where it never ran."""
    d = read()
    s = d["spans"].get(name) if d else None
    if not s or not s["count"]:
        return None
    return 1e3 * s[part] / s["count"]


def counter(name: str) -> Optional[float]:
    d = read()
    return d["counters"].get(name) if d else None
