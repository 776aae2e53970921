"""Graph500 Kronecker graphs (specification v3, kernels 1 and 3).

The edge list follows the specification's reference generator: each of
`edge_factor * 2**scale` edges descends `scale` levels of the 2x2
initiator [[A, B], [C, D]], picking a quadrant per level; vertex labels
are then permuted at random, so that no label order carries the degree
order.  The graph is undirected: self-loops and repeated edges are
removed, and each remaining edge keeps one weight drawn from U[0, 1),
used in both directions (SSSP, kernel 3).

Everything comes from one `numpy.random.Generator`, so a seed fixes the
graph bit for bit.  The result is a plain symmetric CSR in numpy; the
benchmark's references read it directly and the drivers hand the same
arrays to the program.

`reblock` gives a run its own copy of one such graph and its search
keys: vertex labels permuted by whole blocks and within each block, every
edge keeping its weight.  The copy is isomorphic and keeps the multiset of
(source block, destination block) pairs, so every seed gives the program
the same shapes (and finds its compiled programs in the cache) and the
same work, while the placement of every vertex comes from the seed.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """Symmetric CSR: the out-edges of u are indices[indptr[u]:indptr[u+1]],
    destination-ascending, with one weight per direction of an edge."""

    n: int
    indptr: np.ndarray    # [n + 1] int64
    indices: np.ndarray   # [2 * m] int32
    weights: np.ndarray   # [2 * m] float32
    #: the search keys (roots of degree >= 1) in this copy's labels
    keys: Optional[np.ndarray] = None

    @property
    def degree(self) -> np.ndarray:
        return np.diff(self.indptr)

    @property
    def src(self) -> np.ndarray:
        return np.repeat(np.arange(self.n, dtype=np.int64), self.degree)


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, rng: np.random.Generator) -> np.ndarray:
    """[2, M] int64 start/end vertices, before permutation (the spec's
    `kronecker_generator`: per level, the row bit is set with
    probability C + D, the column bit with C/(C+D) or B/(A+B))."""
    m = edge_factor * (1 << scale)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, m), dtype=np.int64)
    for level in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << level
        ij[1] += jj.astype(np.int64) << level
    return ij


def graph500(scale: int, edge_factor: int, a: float, b: float, c: float,
             seed: int) -> Graph:
    """The undirected, label-permuted, weighted Kronecker graph of one
    seed (see the module docstring)."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    ij = kronecker_edges(scale, edge_factor, a, b, c, rng)
    perm = rng.permutation(n)
    u, v = perm[ij[0]], perm[ij[1]]
    w = rng.random(u.shape[0], dtype=np.float32)       # U[0, 1)
    keep = u != v
    u, v, w = u[keep], v[keep], w[keep]
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    # one undirected edge per {lo, hi}: the first drawn keeps its weight
    key = lo * n + hi
    _, first = np.unique(key, return_index=True)
    return _csr(n, lo[first], hi[first], w[first])


def _csr(n: int, lo: np.ndarray, hi: np.ndarray, w: np.ndarray) -> Graph:
    """Symmetric CSR of undirected edges {lo, hi}, one weight each."""
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    wt = np.concatenate([w, w])
    order = np.lexsort((dst, src))
    src, dst, wt = src[order], dst[order], wt[order]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return Graph(n=n, indptr=indptr, indices=dst.astype(np.int32),
                 weights=wt.astype(np.float32))


def reblock(g: Graph, block: int, rng: np.random.Generator) -> Graph:
    """An isomorphic copy of `g` (see the module docstring): labels
    permuted by whole blocks of `block` vertices and within each block;
    each edge keeps its weight, and `keys` follow their vertices."""
    if g.n % block:
        raise ValueError(f"n={g.n} is not a multiple of the block {block}")
    nb = g.n // block
    within = np.argsort(rng.random((nb, block)), axis=1)
    label = (rng.permutation(nb)[:, None] * block + within).reshape(-1)
    u, v = g.src, g.indices.astype(np.int64)
    keep = u < v
    lo, hi = label[u[keep]], label[v[keep]]
    out = _csr(g.n, np.minimum(lo, hi), np.maximum(lo, hi), g.weights[keep])
    keys = None if g.keys is None else label[g.keys]
    return dataclasses.replace(out, keys=keys)


def search_keys(g: Graph, count: int, rng: np.random.Generator
                ) -> np.ndarray:
    """`count` distinct roots among the vertices of degree >= 1 (the
    spec's search keys)."""
    cand = np.flatnonzero(g.degree > 0)
    return rng.choice(cand, size=count, replace=False).astype(np.int64)


def block_pairs(g: Graph, block: int) -> int:
    """P: the (source block, destination block) pairs that hold an edge
    when vertices are blocked `block` at a time in label order."""
    nb = -(-g.n // block)
    return int(np.unique(g.src // block * nb + g.indices // block).size)
