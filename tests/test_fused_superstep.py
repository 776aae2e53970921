"""Fused superstep megakernel + sparse block-pair tests.

Covers the bugfix acceptance criteria:
  * the megakernel (select -> stage -> multi-job push in ONE Pallas
    program over destination-sorted BlockPairs) matches the jnp
    oracle — bitwise for min-plus, float-tolerance for plus-times;
  * `BlockPairs` construction invariants (dst-sorted runs, first/last
    flags, src_nnz real-byte accounting, dense_op faithfulness, the
    edgeless inert pad pair);
  * interpret-resolution has ONE source of truth (`kernels.common`):
    interpret=None means interpret iff backend != "tpu", for every
    kernel entry point (the silent-interpret bug regression);
  * scatter drop-mode parity: sentinel (out-of-range) neighbour ids are
    DROPPED identically by the kernel route and the vmapped engine push;
  * job chunks are J or a multiple of 8 (the TPU block tiling); a tight
    VMEM budget degrades the chunk and the kernel still validates;
  * pair lists longer than one call's SMEM budget chain exactly;
  * padded selection slots aliasing block 0 must not re-push block 0;
  * `tile_pair_loads` (real adjacency bytes) agrees between the host and
    device drivers and across the kernel/jnp push routes.
"""

import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.graph import (CSRGraph, build_blocked, build_block_pairs,
                         rmat_graph, uniform_graph)
from repro.kernels import common
from repro.kernels.fused_superstep import ops as fused_ops
from repro.kernels.fused_superstep.kernel import fused_superstep_call
from repro.kernels.fused_superstep.ops import _pick_job_block, fused_push
from repro.kernels.fused_superstep.ref import fused_superstep_ref
from repro.core.push import push_min_one, push_plus_one, shared_push_fn


def _pairs_for(semiring, n=150, deg=4, vb=16, seed=13):
    if semiring == "plus_times":
        csr = rmat_graph(n, deg, seed=seed)
        g = build_blocked(csr, vb, fill=0.0, normalize="out_degree")
    else:
        csr = uniform_graph(n, deg, seed=seed, weighted=True, w_max=7.0)
        g = build_blocked(csr, vb, fill=float(np.inf))
    return g, build_block_pairs(g)


def _rand_state(rng, j, bn, vb, semiring):
    if semiring == "plus_times":
        d = rng.standard_normal((j, bn, vb)).astype(np.float32)
        base = rng.standard_normal((j, bn, vb)).astype(np.float32)
        return jnp.asarray(d), jnp.asarray(base), None
    d = (rng.random((j, bn, vb)) * 10).astype(np.float32)
    d[rng.random(d.shape) < 0.5] = np.inf          # non-pending vertices
    vals = (rng.random((j, bn, vb)) * 10).astype(np.float32)
    base = np.where(rng.random((j, bn, vb)) < 0.5, vals, np.inf)
    return jnp.asarray(d), jnp.asarray(base), jnp.asarray(vals)


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("jb", [None, 1, 2])
@pytest.mark.parametrize("j", [1, 4, 6])
def test_fused_kernel_matches_ref_plus_times(j, jb):
    if jb is not None and j % jb:
        pytest.skip("job_block must divide J")
    g, bp = _pairs_for("plus_times")
    rng = np.random.default_rng(j * 10 + (jb or 0))
    d, base, _ = _rand_state(rng, j, g.num_blocks, g.block_size,
                             "plus_times")
    out = fused_superstep_call(
        bp.src, bp.dst, bp.first, bp.last, d, base, bp.tiles,
        semiring="plus_times", job_block=jb, interpret=True)
    r_out = fused_superstep_ref(
        bp.src, bp.dst, bp.first, bp.last, d, base, bp.tiles,
        semiring="plus_times")
    # untouched destination blocks pass through the aliased base
    np.testing.assert_allclose(np.asarray(out), np.asarray(r_out),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("jb", [None, 1, 3])
def test_fused_kernel_matches_ref_min_plus_bitwise(jb):
    """Min is exact in any evaluation order: the kernel's fused per-pair
    min-fold must be BIT-EQUAL to the oracle's scatter-min."""
    j = 6
    g, bp = _pairs_for("min_plus")
    rng = np.random.default_rng(7 + (jb or 0))
    d, base, vals = _rand_state(rng, j, g.num_blocks, g.block_size,
                                "min_plus")
    vo, do = fused_superstep_call(
        bp.src, bp.dst, bp.first, bp.last, d, base, bp.tiles,
        values=vals, semiring="min_plus", job_block=jb, interpret=True)
    r_vo, r_do = fused_superstep_ref(
        bp.src, bp.dst, bp.first, bp.last, d, base, bp.tiles,
        values=vals, semiring="min_plus")
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(r_vo))
    np.testing.assert_array_equal(np.asarray(do), np.asarray(r_do))


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
def test_fused_kernel_pair_chunks_chain_exactly(monkeypatch, semiring):
    """A pair list longer than one call's SMEM budget is swept by a chain
    of calls whose chunk edges split dst runs: the chained result equals
    the oracle (min-plus bitwise)."""
    from repro.kernels.fused_superstep import kernel as fk
    g, bp = _pairs_for(semiring, n=300, deg=6)
    chunk = 7                       # splits most dst runs across calls
    assert bp.num_pairs > 3 * chunk
    monkeypatch.setattr(fk, "PAIR_CHUNK", chunk)
    fk._fused_jit.clear_cache()
    rng = np.random.default_rng(21)
    d, base, vals = _rand_state(rng, 4, g.num_blocks, g.block_size,
                                semiring)
    try:
        got = fused_superstep_call(
            bp.src, bp.dst, bp.first, bp.last, d, base, bp.tiles,
            values=vals, semiring=semiring, interpret=True)
    finally:
        fk._fused_jit.clear_cache()
    want = fused_superstep_ref(
        bp.src, bp.dst, bp.first, bp.last, d, base, bp.tiles,
        values=vals, semiring=semiring)
    if semiring == "min_plus":
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
def test_fused_push_matches_vmapped_engine_push(semiring):
    """The megakernel route == the per-job vmapped engine push on a real
    selection (min-plus bitwise; plus-times within contraction-order
    tolerance)."""
    g, bp = _pairs_for(semiring)
    rng = np.random.default_rng(3)
    j, bn, vb = 4, g.num_blocks, g.block_size
    _, deltas, vals = _rand_state(rng, j, bn, vb, "min_plus")
    if semiring == "plus_times":
        vals = jnp.asarray(rng.random((j, bn, vb)), jnp.float32)
        deltas = jnp.asarray(rng.random((j, bn, vb)), jnp.float32)
    sel = jnp.asarray([0, 2, 5, 7], jnp.int32)
    msk = jnp.ones(4, jnp.float32)
    scales = jnp.asarray(rng.random(j), jnp.float32)
    push_one = push_plus_one if semiring == "plus_times" else push_min_one
    v1, d1 = jax.vmap(push_one, in_axes=(0, 0, None, None, None, None, 0))(
        vals, deltas, g.tiles, g.nbr_ids, sel, msk, scales)
    v2, d2 = fused_push(vals, deltas, bp, sel, msk, scales,
                        semiring=semiring, interpret=True)
    if semiring == "min_plus":
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    else:
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-6)


def test_shared_push_fn_pair_emulation_matches_vmap():
    """use_pallas=False plus-times pair sweep (per-(job, pair) einsum +
    scatter-add) == the vmapped push_one it replaced."""
    g, bp = _pairs_for("plus_times")
    rng = np.random.default_rng(11)
    j, bn, vb = 3, g.num_blocks, g.block_size
    vals = jnp.asarray(rng.random((j, bn, vb)), jnp.float32)
    dels = jnp.asarray(rng.random((j, bn, vb)), jnp.float32)
    sel = jnp.asarray([1, 4, 6], jnp.int32)
    msk = jnp.ones(3, jnp.float32)
    scales = jnp.asarray(rng.random(j), jnp.float32)
    fn = shared_push_fn("plus_times", push_plus_one, use_pallas=False)
    v1, d1 = fn(vals, dels, g.tiles, g.nbr_ids, sel, msk, scales, None, None)
    v2, d2 = fn(vals, dels, g.tiles, g.nbr_ids, sel, msk, scales, None, bp)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# BlockPairs construction
# ---------------------------------------------------------------------------

def test_block_pairs_invariants():
    g, bp = _pairs_for("plus_times", seed=5)
    src, dst, slot = map(np.asarray, (bp.src, bp.dst, bp.slot))
    first, last = np.asarray(bp.first), np.asarray(bp.last)
    ids, msk = np.asarray(g.nbr_ids), np.asarray(g.nbr_mask)
    assert bp.num_pairs == int(msk.sum())
    assert (np.diff(dst) >= 0).all()                     # dst-sorted
    assert (ids[src, slot] == dst).all()                 # slot consistency
    # first/last mark exactly the dst-run boundaries
    np.testing.assert_array_equal(first[1:], (dst[1:] != dst[:-1]))
    assert first[0] == 1 and last[-1] == 1
    np.testing.assert_array_equal(last[:-1], first[1:])
    # src_nnz counts real pairs per SOURCE block (tile_pair_loads unit)
    np.testing.assert_array_equal(np.asarray(bp.src_nnz),
                                  msk.sum(axis=1).astype(np.int32))
    # pair tiles are the real ELL tiles, in pair order
    np.testing.assert_array_equal(np.asarray(bp.tiles),
                                  np.asarray(g.tiles)[src, slot])


def test_block_pairs_dense_op_reconstructs_operator():
    g, bp = _pairs_for("plus_times", n=100, deg=6, vb=16, seed=2)
    if bp.dense_op is None:
        pytest.skip("graph below dense_op density threshold")
    bn, vb = g.num_blocks, g.block_size
    dense = np.zeros((bn, vb, bn, vb), np.float32)
    src, dst = np.asarray(bp.src), np.asarray(bp.dst)
    dense[src, :, dst, :] = np.asarray(bp.tiles)
    np.testing.assert_array_equal(np.asarray(bp.dense_op),
                                  dense.reshape(bn * vb, bn * vb))


def test_block_pairs_edgeless_pad_pair_is_inert():
    csr = CSRGraph.from_edges(40, [], [])
    for fill, semiring in ((0.0, "plus_times"), (float(np.inf), "min_plus")):
        g = build_blocked(csr, 16, fill=fill)
        bp = build_block_pairs(g)
        assert bp.num_pairs == 1
        assert int(np.asarray(bp.src_nnz).sum()) == 0
        rng = np.random.default_rng(0)
        d, base, vals = _rand_state(rng, 2, g.num_blocks, 16, semiring)
        v, dl = fused_push(vals if vals is not None
                           else jnp.zeros_like(base), base, bp,
                           jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.float32),
                           jnp.ones(2, jnp.float32), semiring=semiring,
                           interpret=True)
        # nothing selected, nothing touched: state passes through
        np.testing.assert_array_equal(np.asarray(dl), np.asarray(base))


# ---------------------------------------------------------------------------
# interpret resolution (silent-interpret regression)
# ---------------------------------------------------------------------------

def test_interpret_resolves_false_on_tpu_backend(monkeypatch):
    """interpret=None must mean interpret=False when the backend is a real
    TPU — the one-source-of-truth rule in kernels.common.  (The old
    mj_spmm_call defaulted interpret=True unconditionally: a TPU caller
    bypassing ops.mj_spmm silently ran the interpreter.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert common.default_interpret() is False
    assert common.resolve_interpret(None) is False
    assert common.resolve_interpret(True) is True

    seen = {}

    def spy(*a, **kw):
        seen["interpret"] = kw["interpret"]
        raise RuntimeError("stop")

    import repro.kernels.mj_spmm.kernel as mjk
    monkeypatch.setattr(mjk, "_mj_spmm_jit", spy)
    with pytest.raises(RuntimeError):
        mjk.mj_spmm_call(jnp.zeros((1, 2, 8)), jnp.zeros((1, 1, 8, 8)))
    assert seen["interpret"] is False

    import repro.kernels.priority_pairs.kernel as ppk
    monkeypatch.setattr(ppk, "_pairs_jit", spy)
    with pytest.raises(RuntimeError):
        ppk.priority_pairs_call(jnp.zeros((1, 2, 8)))
    assert seen["interpret"] is False


def test_interpret_resolves_true_off_tpu():
    assert jax.default_backend() != "tpu"
    assert common.default_interpret() is True
    assert common.resolve_interpret(None) is True
    assert common.resolve_interpret(False) is False


# ---------------------------------------------------------------------------
# scatter drop-mode parity (sentinel neighbour ids)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
def test_push_shared_drops_sentinel_neighbors_like_vmap(semiring):
    """Out-of-range neighbour ids (sentinel BN) must be DROPPED by the
    kernel route's scatter exactly as by the engine push — min-plus
    bitwise.  (The old plus-times scatter omitted mode="drop", leaving
    the sentinel behavior unspecified rather than aligned.)"""
    from repro.kernels.mj_spmm.ops import push_shared
    rng = np.random.default_rng(4)
    J, BN, VB, K = 3, 6, 16, 3
    tiles = np.where(rng.random((BN, K, VB, VB)) < 0.7, 0.0,
                     rng.random((BN, K, VB, VB))).astype(np.float32)
    nbr = rng.integers(0, BN, (BN, K)).astype(np.int32)
    nbr[:, -1] = BN                 # sentinel slot: out of range -> dropped
    nbr = jnp.asarray(nbr)
    if semiring == "min_plus":
        tiles = np.where(tiles == 0.0, np.inf, tiles)
    tiles = jnp.asarray(tiles)
    sel = jnp.asarray([0, 2, 4], jnp.int32)
    msk = jnp.ones(3, jnp.float32)
    scale = jnp.asarray(rng.random(J), jnp.float32)
    if semiring == "plus_times":
        vals = jnp.asarray(rng.random((J, BN, VB)), jnp.float32)
        dels = jnp.asarray(rng.random((J, BN, VB)), jnp.float32)
        push_one = push_plus_one
    else:
        vals = jnp.asarray(rng.random((J, BN, VB)) * 10, jnp.float32)
        dels = jnp.where(jnp.asarray(rng.random((J, BN, VB))) < 0.5,
                         vals, jnp.inf)
        push_one = push_min_one
    v1, d1 = jax.vmap(push_one, in_axes=(0, 0, None, None, None, None, 0))(
        vals, dels, tiles, nbr, sel, msk, scale)
    v2, d2 = push_shared(vals, dels, tiles, nbr, sel, msk, scale,
                         semiring=semiring, interpret=True)
    if semiring == "min_plus":
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    else:
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# job-chunk degradation + padded-slot aliasing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
def test_prime_job_count_degrades_chunk_and_validates(monkeypatch, semiring):
    """Job chunks must be J or a multiple of 8 (Mosaic tiles a block's
    last two dims).  Under a tight VMEM budget J=24 degrades to the
    8-job chunk, within budget, and validates; a prime J=13 has no legal
    chunk but the whole axis."""
    from repro.analysis import contracts as C
    from repro.kernels.fused_superstep.ops import block_vmem_bytes
    vb = 16
    budget = block_vmem_bytes(12, vb, semiring)   # room for 12, not 24
    monkeypatch.setattr(common, "VMEM_BUDGET", budget)
    assert _pick_job_block(24, vb, semiring) == 8
    assert C.fused_superstep_vmem_bytes(24, vb, semiring) <= budget
    assert _pick_job_block(13, vb, semiring) == 13

    g, bp = _pairs_for(semiring, vb=vb)
    rng = np.random.default_rng(9)
    d, base, vals = _rand_state(rng, 24, g.num_blocks, vb, semiring)
    sel = jnp.asarray([0, 2, 5], jnp.int32)
    msk = jnp.ones(3, jnp.float32)
    scales = jnp.ones(24, jnp.float32)
    push_one = push_plus_one if semiring == "plus_times" else push_min_one
    if vals is None:
        vals = jnp.zeros_like(base)
    v1, d1 = jax.vmap(push_one, in_axes=(0, 0, None, None, None, None, 0))(
        vals, base, g.tiles, g.nbr_ids, sel, msk, scales)
    v2, d2 = fused_push(vals, base, bp, sel, msk, scales,
                        semiring=semiring, interpret=True)
    if semiring == "min_plus":
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    else:
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
def test_padded_selection_slot_aliasing_block0(semiring):
    """A padded selection slot aliases block 0 (sel id 0, mask 0).  With
    block 0 ITSELF selected in a live slot, the padded alias must not
    re-push block 0 — parity with the mask-aware engine push."""
    g, bp = _pairs_for(semiring)
    rng = np.random.default_rng(6)
    j, bn, vb = 3, g.num_blocks, g.block_size
    _, base, vals = _rand_state(rng, j, bn, vb, "min_plus")
    if semiring == "plus_times":
        vals = jnp.asarray(rng.random((j, bn, vb)), jnp.float32)
        base = jnp.asarray(rng.random((j, bn, vb)), jnp.float32)
    sel = jnp.asarray([0, 3, 0], jnp.int32)       # slot 2 pads onto block 0
    msk = jnp.asarray([1.0, 1.0, 0.0], jnp.float32)
    scales = jnp.asarray(rng.random(j), jnp.float32)
    push_one = push_plus_one if semiring == "plus_times" else push_min_one
    v1, d1 = jax.vmap(push_one, in_axes=(0, 0, None, None, None, None, 0))(
        vals, base, g.tiles, g.nbr_ids, sel, msk, scales)
    v2, d2 = fused_push(vals, base, bp, sel, msk, scales,
                        semiring=semiring, interpret=True)
    if semiring == "min_plus":
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    else:
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# mj_spmm HBM-fetch accounting (the corrected BlockSpec residency story)
# ---------------------------------------------------------------------------

def test_mj_spmm_hbm_fetch_counts_per_grid_step_d_fetches(monkeypatch):
    """The d-chunk's index (i, jt) changes at (almost) every grid step, so
    d is re-fetched k times per job chunk — NOT kept resident across k as
    the old BlockSpec comment claimed.  Only the j/jb == 1 degenerate
    grid keeps d resident."""
    from repro.analysis import contracts as C
    import repro.kernels.mj_spmm.ops as mj_ops
    q, k, vb = 5, 3, 32
    # ample budget: jb == j -> jt == 1, d IS resident across k
    assert mj_ops._pick_job_block(8, vb) == 8
    assert (C.mj_spmm_hbm_fetch_bytes(q, k, 8, vb)
            == q * 1 * 8 * vb * 4 + q * k * vb * vb * 4)
    # tight budget: jb == 4 -> jt == 2, every d chunk fetched k times
    fixed = 2 * vb * vb * 4
    per_job = 2 * vb * 4
    monkeypatch.setattr(mj_ops, "_VMEM_BUDGET", fixed + 4 * per_job)
    assert mj_ops._pick_job_block(8, vb) == 4
    assert (C.mj_spmm_hbm_fetch_bytes(q, k, 8, vb)
            == q * k * 2 * 4 * vb * 4 + q * k * vb * vb * 4)


# ---------------------------------------------------------------------------
# pair-loads accounting across drivers
# ---------------------------------------------------------------------------

def test_tile_pair_loads_consistent_across_drivers():
    """tile_pair_loads (real nonzero pairs staged) must agree between the
    host driver, the device driver, and the kernel/jnp push routes — the
    selections are identical, so the real bytes moved are too."""
    from repro.algorithms import PageRank, SSSP
    from repro.core import GraphSession, TwoLevel

    csr = rmat_graph(150, 4, seed=13)
    loads = {}
    for label, use_pallas, policy in [
        ("host", False, TwoLevel()),
        ("host_k", True, TwoLevel()),
        ("dev", False, TwoLevel(backend="device", steps_per_sync=math.inf)),
    ]:
        sess = GraphSession(csr, 16, capacity=2, seed=5,
                            use_pallas=use_pallas)
        sess.submit(PageRank())
        sess.submit(SSSP(source=3))
        m = sess.run(policy, 20000)
        assert m.converged
        loads[label] = (m.supersteps, m.tile_loads, m.tile_pair_loads)
    assert loads["host"][2] > 0
    assert loads["host"] == loads["host_k"] == loads["dev"]
    # the pair accounting is finer than block staging: a staged block
    # moves src_nnz >= 0 pairs, bounded by K per block
    sup, tl, tpl = loads["host"]
    assert tpl <= tl * 16
    assert "tile_pair_loads" in m.to_dict()
