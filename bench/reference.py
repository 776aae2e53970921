"""Plain references for the benchmark's queries, and their controls.

The references read only the benchmark's own CSR (`bench.graph500.Graph`)
and import nothing of the program:

  sssp  scipy's Dijkstra in float64 over the weighted graph; +inf where
        a vertex is unreachable.
  ppr   personalised PageRank by power iteration in float64:
        x = (1 - d) e_s + d W^T x, W the out-degree-normalised adjacency.

The controls are the same references in the nearest precision below the
one the configurations state, as a later change to the program might
compute them:

  sssp_bf16   Jacobi Bellman-Ford with distances and weights in bfloat16
              (the min-plus push states float32 on the VPU);
  ppr_high    the power iteration with every product taken in three
              bfloat16 passes and float32 sums, which is what
              `Precision.HIGH` computes on the MXU (the plus-times kernel
              states float32 at `Precision.HIGHEST`).  The passes are
              spelled out, so the control reads the same on any backend.
"""

from __future__ import annotations

import numpy as np

from bench.graph500 import Graph

#: power iteration stops once no entry moves by more than this (float64)
PPR_STOP = 1e-13
PPR_MAX_ITERS = 10_000


def sssp(g: Graph, roots) -> np.ndarray:
    """[len(roots), n] float64 shortest distances (+inf: unreachable)."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra
    a = sp.csr_matrix((g.weights.astype(np.float64), g.indices, g.indptr),
                      shape=(g.n, g.n))
    # csgraph keeps explicit zeros as edges: a weight drawn as 0.0 stays
    return dijkstra(a, directed=True, indices=np.asarray(roots))


def _ppr_operator(g: Graph):
    import scipy.sparse as sp
    deg = np.maximum(g.degree, 1).astype(np.float64)
    w = 1.0 / deg[g.src]
    # transpose: row v of W^T sums the pushes into v
    return sp.csr_matrix((w, (g.indices, g.src)), shape=(g.n, g.n))


def ppr(g: Graph, sources, damping: float) -> np.ndarray:
    """[len(sources), n] float64 personalised PageRank vectors."""
    wt = _ppr_operator(g)
    k = len(sources)
    b = np.zeros((g.n, k))
    b[np.asarray(sources), np.arange(k)] = 1.0 - damping
    x = b.copy()
    for _ in range(PPR_MAX_ITERS):
        nxt = b + damping * (wt @ x)
        if np.max(np.abs(nxt - x)) < PPR_STOP:
            return nxt.T
        x = nxt
    raise RuntimeError("power iteration did not converge")


def ppr_shortfall(g: Graph, damping: float) -> np.ndarray:
    """[n] u = (I - d W^T)^-1 d W^T 1, float64.  A push fixpoint whose
    pending deltas r all lie in [0, tol) falls short of the true vector
    by (I - d W^T)^-1 d W^T r, which is at most tol * u at each vertex."""
    wt = _ppr_operator(g)
    ones = np.ones(g.n)
    u = np.zeros(g.n)
    for _ in range(PPR_MAX_ITERS):
        nxt = damping * (wt @ (ones + u))
        if np.max(np.abs(nxt - u)) < PPR_STOP:
            return nxt
        u = nxt
    raise RuntimeError("power iteration did not converge")


# ---------------------------------------------------------------------------
# controls (JAX: they run on the chip at the cells' own sizes)
# ---------------------------------------------------------------------------


def sssp_bf16(g: Graph, roots) -> np.ndarray:
    """[len(roots), n] Bellman-Ford distances computed in bfloat16."""
    import jax
    import jax.numpy as jnp
    src = jnp.asarray(g.src, jnp.int32)
    dst = jnp.asarray(g.indices, jnp.int32)
    w = jnp.asarray(g.weights, jnp.bfloat16)
    k = len(roots)
    d0 = jnp.full((k, g.n), jnp.inf, jnp.bfloat16)
    d0 = d0.at[jnp.arange(k), jnp.asarray(roots, jnp.int32)].set(0)

    def relax(state):
        d, _ = state
        cand = d[:, src] + w[None, :]
        best = jax.vmap(lambda c: jax.ops.segment_min(
            c, dst, num_segments=g.n, indices_are_sorted=False))(cand)
        new = jnp.minimum(d, best)
        return new, jnp.any(new != d)

    out, _ = jax.jit(lambda d: jax.lax.while_loop(
        lambda s: s[1], relax, (d, jnp.bool_(True))))(d0)
    return np.asarray(jax.device_get(out), np.float64)


def _bf16_split(x):
    import jax.numpy as jnp
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi.astype(jnp.float32), lo.astype(jnp.float32)


def ppr_high(g: Graph, sources, damping: float, iters: int = 400
             ) -> np.ndarray:
    """[len(sources), n] personalised PageRank with products in three
    bfloat16 passes (hi*hi + hi*lo + lo*hi) and float32 sums."""
    import jax
    import jax.numpy as jnp
    src = jnp.asarray(g.src, jnp.int32)
    dst = jnp.asarray(g.indices, jnp.int32)
    deg = np.maximum(g.degree, 1).astype(np.float32)
    w_hi, w_lo = _bf16_split(jnp.asarray(1.0 / deg[g.src], jnp.float32))
    k = len(sources)
    b = jnp.zeros((k, g.n), jnp.float32).at[
        jnp.arange(k), jnp.asarray(sources, jnp.int32)].set(1.0 - damping)

    def step(x, _):
        x_hi, x_lo = _bf16_split(x[:, src])
        prod = x_hi * w_hi + (x_hi * w_lo + x_lo * w_hi)
        pushed = jax.vmap(lambda p: jax.ops.segment_sum(
            p, dst, num_segments=g.n))(prod)
        return b + damping * pushed, None

    out, _ = jax.jit(lambda x: jax.lax.scan(step, x, None,
                                            length=iters))(b)
    return np.asarray(jax.device_get(out), np.float64)
