"""The Graph500 Kronecker generator: deterministic, undirected, permuted,
no self-loops or repeated edges, one U[0, 1) weight per edge."""

import dataclasses

import numpy as np
import pytest

import bench_testlib  # noqa: F401  (path set-up)
from bench.graph500 import graph500, kronecker_edges, search_keys

ARGS = dict(scale=10, edge_factor=16, a=0.57, b=0.19, c=0.19)


@pytest.fixture(scope="module")
def g():
    return graph500(seed=3, **ARGS)


def test_same_seed_same_graph(g):
    h = graph500(seed=3, **ARGS)
    for name in ("indptr", "indices", "weights"):
        np.testing.assert_array_equal(getattr(g, name), getattr(h, name))
    other = graph500(seed=4, **ARGS)
    assert not np.array_equal(g.indices[:100], other.indices[:100])


def test_symmetric_one_weight_per_edge(g):
    fwd = {(int(u), int(v)): float(w)
           for u, v, w in zip(g.src, g.indices, g.weights)}
    assert len(fwd) == len(g.indices)                 # no repeated edge
    assert all(fwd[(v, u)] == w for (u, v), w in fwd.items())
    assert all(u != v for u, v in fwd)                # no self-loop
    assert np.all((g.weights >= 0.0) & (g.weights < 1.0))


def test_rows_sorted_and_sized(g):
    assert g.n == 1 << ARGS["scale"]
    assert g.indptr[-1] == len(g.indices)
    for u in range(0, g.n, 97):
        row = g.indices[g.indptr[u]:g.indptr[u + 1]]
        assert np.all(np.diff(row) > 0)
    # repeated draws collapse: fewer than 2 * M directed edges remain
    assert len(g.indices) < 2 * ARGS["edge_factor"] * g.n


def test_labels_are_permuted(g):
    # unpermuted, vertex 0 takes the A quadrant at every level and has the
    # highest degree; the permutation moves it elsewhere
    rng = np.random.default_rng(0)
    ij = kronecker_edges(ARGS["scale"], ARGS["edge_factor"], ARGS["a"],
                         ARGS["b"], ARGS["c"], rng)
    raw_deg = np.bincount(ij.ravel(), minlength=g.n)
    assert int(np.argmax(raw_deg)) == 0
    assert int(np.argmax(g.degree)) != 0
    assert np.sum(g.degree == 0) > 0          # isolated vertices remain


def test_search_keys_have_edges(g):
    keys = search_keys(g, 64, np.random.default_rng(1))
    assert len(set(keys.tolist())) == 64
    assert np.all(g.degree[keys] > 0)


def test_reblock_keeps_the_block_pair_shapes(g):
    from bench.graph500 import block_pairs, reblock
    base = dataclasses.replace(
        g, keys=search_keys(g, 64, np.random.default_rng(0)))
    a = reblock(base, 64, np.random.default_rng(1))
    b = reblock(base, 64, np.random.default_rng(2))
    assert block_pairs(a, 64) == block_pairs(b, 64) == block_pairs(g, 64)
    for h in (a, b):
        assert len(h.indices) == len(g.indices)
        assert np.array_equal(np.sort(h.degree), np.sort(g.degree))
        assert np.array_equal(np.sort(h.weights), np.sort(g.weights))
        fwd = {(int(u), int(v)): float(w)
               for u, v, w in zip(h.src, h.indices, h.weights)}
        assert all(fwd[(v, u)] == w for (u, v), w in fwd.items())
        # each key keeps its degree and its multiset of edge weights
        for k0, k1 in zip(base.keys, h.keys):
            r0 = g.weights[g.indptr[k0]:g.indptr[k0 + 1]]
            r1 = h.weights[h.indptr[k1]:h.indptr[k1 + 1]]
            assert np.array_equal(np.sort(r0), np.sort(r1))
    assert not np.array_equal(a.indices, b.indices)
    assert not np.array_equal(a.keys, b.keys)

    # the same ELL width: pairs per source block, as a multiset
    def per_block(h):
        nb = h.n // 64
        keys = np.unique(h.src // 64 * nb + h.indices // 64)
        return np.sort(np.bincount(keys // nb, minlength=nb))
    assert np.array_equal(per_block(a), per_block(g))
