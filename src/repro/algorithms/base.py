"""Delta-based accumulative iterative algorithms (PrIter / paper Eq. 3).

Two semirings cover the paper's algorithm families:

  PLUS_TIMES : v <- v + delta;   new_delta[dst] += push_scale * delta[src] * w
               (PageRank, PPR, Katz, Adsorption, ...)
  MIN_PLUS   : v <- min(v, cand);  cand[dst] = min_src(delta[src] + w)
               (SSSP, BFS, connected components via 0-weight label prop, ...)

State layout is blocked to match `BlockedGraph`:
  values [B_N, Vb]  and  deltas [B_N, Vb]   (per job; engine adds a J axis).

For MIN_PLUS, `deltas` holds the pending-propagation distance (the value at
the time the vertex last improved) and +inf when nothing is pending.

Vertex priority must be POSITIVE with 0 == converged (see DESIGN.md: the
paper's negative SSSP priority breaks its own epsilon/total formulas, so we
use the monotone transform 1/(1+dist)).
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar, Tuple

import jax.numpy as jnp

from repro.graph.structure import BlockedGraph

PLUS_TIMES = "plus_times"
MIN_PLUS = "min_plus"

INF = jnp.float32(jnp.inf)


@dataclasses.dataclass(frozen=True)
class Algorithm:
    """Base class; subclasses override init/vertex_priority as needed."""

    name: str = "abstract"
    semiring: str = PLUS_TIMES
    tolerance: float = 1e-6     # |delta| < tol  ==> vertex converged (plus-times)

    #: the fields that differ from job to job (a query's source).  A
    #: session admits and retires a job with one compiled program per
    #: class and static fields, which takes these as traced arguments: so
    #: `init` and `result` must trace with them (and with the graph's
    #: arrays) as arrays.
    job_fields: ClassVar[Tuple[str, ...]] = ()

    def get_push_scale(self) -> float:
        """Multiplies deltas before the push (PageRank damping, Katz alpha)."""
        return 1.0

    # ---- state -------------------------------------------------------------
    def init(self, g: BlockedGraph) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    # graph build parameters this algorithm requires
    graph_fill: float = 0.0
    graph_normalize: str | None = None
    graph_symmetrize: bool = False

    # ---- priority ----------------------------------------------------------
    def vertex_priority(self, values: jnp.ndarray,
                        deltas: jnp.ndarray) -> jnp.ndarray:
        """Positive priority per vertex; exactly 0 for converged vertices."""
        if self.semiring == PLUS_TIMES:
            p = jnp.abs(deltas)
            return jnp.where(p >= self.tolerance, p, 0.0)
        # MIN_PLUS: pending vertices carry finite delta
        return jnp.where(jnp.isfinite(deltas), 1.0 / (1.0 + deltas), 0.0)

    def unconverged(self, values: jnp.ndarray,
                    deltas: jnp.ndarray) -> jnp.ndarray:
        if self.semiring == PLUS_TIMES:
            return jnp.abs(deltas) >= self.tolerance
        return jnp.isfinite(deltas)

    # ---- final extraction ----------------------------------------------------
    def result(self, values: jnp.ndarray, deltas: jnp.ndarray) -> jnp.ndarray:
        """Algorithm result per vertex (values plus any unfolded deltas)."""
        if self.semiring == PLUS_TIMES:
            return values + deltas
        return values


def _blocked_full(g: BlockedGraph, value: float) -> jnp.ndarray:
    return jnp.full((g.num_blocks, g.block_size), value, dtype=jnp.float32)
