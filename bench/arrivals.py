"""Open-loop arrivals on the wall clock.

The shape follows the program's `repro.obs.loadgen.generate_arrivals`
(seeded Poisson arrivals, tenants, a source vertex and an urgency per
request), but time is in seconds, and the number of requests in a window
is fixed: `round(rate * seconds)` arrival times drawn uniformly over the
window and sorted, which is a Poisson process given its count.  Every
seed then offers the same amount of work, in another order.

Sources are Zipf(s) over a seeded permutation of the vertices that have
an edge: the k-th most popular source is drawn with weight 1 / k**s.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float     # seconds after the window opens
    tenant: int
    source: int
    urgency: float


def zipf_sources(candidates: np.ndarray, count: int, exponent: float,
                 rng: np.random.Generator) -> np.ndarray:
    order = rng.permutation(candidates)
    p = 1.0 / np.arange(1, len(order) + 1, dtype=np.float64) ** exponent
    return order[rng.choice(len(order), size=count, p=p / p.sum())]


def open_loop(rate: float, seconds: float, candidates: np.ndarray,
              zipf_exponent: float, n_tenants: int,
              rng: np.random.Generator) -> list:
    """The window's arrivals, sorted by due time."""
    count = int(round(rate * seconds))
    due = np.sort(rng.random(count) * seconds)
    sources = zipf_sources(candidates, count, zipf_exponent, rng)
    tenants = rng.integers(n_tenants, size=count)
    urgency = np.round(rng.uniform(0.1, 1.0, size=count), 6)
    return [Arrival(float(d), int(t), int(s), float(u))
            for d, t, s, u in zip(due, tenants, sources, urgency)]
