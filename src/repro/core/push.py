"""Device-side push primitives for one job (vmapped over jobs above).

A "push" processes the selected adjacency blocks for one job: it consumes
the pending deltas of the selected blocks and scatters their contributions
into the neighbours' deltas (paper Eq. 3, both semirings).  These are pure
functions of stacked [B_N, Vb] state, shared by every schedule policy and
by the pod-scale dry-run (repro.launch.graph_dryrun).

Evolving graphs (repro.stream) stage a bounded per-block delta-COO
overlay alongside each tile (graph.structure.TileOverlay): a push of
block b consumes b's pending deltas through the base tile AND through
b's overlay edges in the same staging.  Every push takes the overlay as
its trailing argument; the all-inert capacity-0 overlay of a
never-updated view contributes exact no-ops (plus-times adds 0.0,
min-plus mins inf), keeping frozen-graph runs bitwise identical.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.algorithms.base import Algorithm
from repro.core import priority as prio
from repro.graph.structure import TileOverlay, empty_overlay

__all__ = [
    "push_plus_one", "push_min_one", "compute_pairs",
    "shared_push_fn", "indep_push_fn",
    "overlay_push_plus", "overlay_push_min",
]


def _block_mask(sel_ids: jnp.ndarray, sel_mask: jnp.ndarray,
                num_blocks: int) -> jnp.ndarray:
    """[q] ids + validity mask -> dense [B_N] bool, scatter-hazard free."""
    m = jnp.zeros((num_blocks,), dtype=jnp.bool_)
    return m.at[sel_ids].max(sel_mask > 0)


def _overlay_rows(ov: TileOverlay, sel_ids: jnp.ndarray):
    """Gather the overlay rows of the selected blocks: [q, C] each."""
    return ov.src_u[sel_ids], ov.dst[sel_ids], ov.w[sel_ids], ov.mask[sel_ids]


def overlay_push_plus(deltas: jnp.ndarray, d_sel: jnp.ndarray,
                      ov: TileOverlay, sel_ids: jnp.ndarray) -> jnp.ndarray:
    """Scatter the selected blocks' overlay contributions into `deltas`.

    d_sel [q, Vb] must be the SAME consumed-and-scaled deltas the base
    tile push used (pre-consumption values), so an overlay edge pushes
    exactly once per staging, in lockstep with the tile."""
    if ov.capacity == 0:
        return deltas
    src_u, dst, w, mask = _overlay_rows(ov, sel_ids)          # [q, C]
    q = sel_ids.shape[0]
    contrib = d_sel[jnp.arange(q)[:, None], src_u] * w * mask  # [q, C]
    flat = deltas.reshape(-1)
    flat = flat.at[dst.reshape(-1)].add(contrib.reshape(-1))
    return flat.reshape(deltas.shape)


def overlay_push_min(values: jnp.ndarray, deltas: jnp.ndarray,
                     d_sel: jnp.ndarray, ov: TileOverlay,
                     sel_ids: jnp.ndarray):
    """Min-plus analogue: relax the selected blocks' overlay edges.

    d_sel [q, Vb] is the consumed pending distance of the selected blocks
    (inf where nothing pends / the slot is padded)."""
    if ov.capacity == 0:
        return values, deltas
    src_u, dst, w, mask = _overlay_rows(ov, sel_ids)          # [q, C]
    q = sel_ids.shape[0]
    cand = jnp.where(mask > 0,
                     d_sel[jnp.arange(q)[:, None], src_u] + w,
                     jnp.inf).reshape(-1)
    idx = dst.reshape(-1)
    vb = values.shape[-1]
    v_flat, d_flat = values.reshape(-1), deltas.reshape(-1)
    old = v_flat[idx]
    v_flat = v_flat.at[idx].min(cand)
    new = v_flat[idx]
    d_flat = d_flat.at[idx].min(jnp.where(new < old, new, jnp.inf))
    return v_flat.reshape(-1, vb), d_flat.reshape(-1, vb)


def push_plus_one(values: jnp.ndarray, deltas: jnp.ndarray,
                  tiles: jnp.ndarray, nbr_ids: jnp.ndarray,
                  sel_ids: jnp.ndarray, sel_mask: jnp.ndarray,
                  push_scale: jnp.ndarray, overlay: TileOverlay = None):
    """One job, PLUS_TIMES semiring. values/deltas [B_N, Vb]."""
    if overlay is None:
        overlay = empty_overlay(values.shape[0])
    consumed = _block_mask(sel_ids, sel_mask, values.shape[0])[:, None]
    raw = jnp.where(consumed, deltas, 0.0)
    # mask padded selection slots: a padded slot aliases block 0 and must not
    # re-push block 0's delta when block 0 is itself selected
    d_sel = raw[sel_ids] * push_scale * sel_mask[:, None]  # [q, Vb]
    t_sel = tiles[sel_ids]                                # [q, K, Vb, Vb]
    contrib = jnp.einsum("qv,qkvw->qkw", d_sel, t_sel)    # [q, K, Vb]
    values = values + raw
    deltas = deltas - raw
    dst = nbr_ids[sel_ids].reshape(-1)                    # [q*K]
    deltas = deltas.at[dst].add(
        contrib.reshape(-1, contrib.shape[-1]), mode="drop")
    deltas = overlay_push_plus(deltas, d_sel, overlay, sel_ids)
    return values, deltas


def push_min_one(values: jnp.ndarray, deltas: jnp.ndarray,
                 tiles: jnp.ndarray, nbr_ids: jnp.ndarray,
                 sel_ids: jnp.ndarray, sel_mask: jnp.ndarray,
                 push_scale: jnp.ndarray, overlay: TileOverlay = None):
    """One job, MIN_PLUS semiring (push_scale unused, kept for signature)."""
    del push_scale
    if overlay is None:
        overlay = empty_overlay(values.shape[0])
    bn = values.shape[0]
    consumed = _block_mask(sel_ids, sel_mask, bn)[:, None]
    d_sel = jnp.where(consumed, deltas, jnp.inf)[sel_ids]   # [q, Vb]
    d_sel = jnp.where(sel_mask[:, None] > 0, d_sel, jnp.inf)
    deltas = jnp.where(consumed, jnp.inf, deltas)

    # one ELL slot k per scan step, gathered inside the step: staging all
    # [q, K, Vb, Vb] selected tiles at once does not fit a 16 GB chip
    # at deployment size (q=282, K=498, Vb=64 needs 2 x 4.6 GB)
    def body(carry, k):
        values, deltas = carry
        t_k = tiles[sel_ids, k]                              # [q, Vb, Vb]
        dst_k = nbr_ids[sel_ids, k]                          # [q]
        contrib = jnp.min(d_sel[:, :, None] + t_k, axis=1)   # [q, Vb]
        old = values[dst_k]
        values = values.at[dst_k].min(contrib)
        new = values[dst_k]
        improved = new < old
        deltas = deltas.at[dst_k].min(jnp.where(improved, new, jnp.inf))
        return (values, deltas), None

    (values, deltas), _ = jax.lax.scan(
        body, (values, deltas), jnp.arange(tiles.shape[1]))
    values, deltas = overlay_push_min(values, deltas, d_sel, overlay, sel_ids)
    return values, deltas


def compute_pairs(alg: Algorithm, values: jnp.ndarray, deltas: jnp.ndarray):
    """[J, B_N, Vb] -> (node_un [J,B_N], p_mean [J,B_N])."""
    p = alg.vertex_priority(values, deltas)
    return prio.block_pairs(p)


def shared_push_fn(semiring: str, push_one, use_pallas: bool):
    """Stacked-job CAJS push callable (un-jitted): all jobs process the
    same [q] selection plus the shared overlay (in_axes None — one
    staging serves every job).  The ONE place the kernel-vs-jnp dispatch
    and the in_axes wiring live — jitted+cached by GraphSession for the
    host driver, inlined into the compiled superstep by the device
    driver.

    Returns fn(values, deltas, tiles, nbr_ids, sel, msk, scales, overlay,
    pairs) with `pairs` the view's `graph.BlockPairs`:

      use_pallas=True   the fused_superstep Pallas megakernel sweeps the
                        destination-sorted pairs (select/stage/push
                        fused per dst block); the overlay ride-along
                        stays in jnp on the PRE-consumption deltas,
                        exactly like every other push path.  Needs the
                        pair view: there is no ELL fallback.
      use_pallas=False  plus-times emulates the same pair sweep in jnp
                        with a per-(job, pair) einsum + scatter-add.
                        Deliberately NOT `pairs.dense_op`: a [J, N] @
                        [N, N] matmul lets XLA pick a J-dependent
                        contraction blocking, which breaks the bit-for-
                        bit job-axis sharding invariance dist.graph
                        guarantees.  min-plus keeps the vmapped per-job
                        `push_one` (its sequential min-scan is the
                        bitwise anchor the fixpoint tests pin).
      pairs=None        (jnp route only) falls back to the vmapped
                        `push_one` (block-ELL staging).
    """
    vm = jax.vmap(push_one, in_axes=(0, 0, None, None, None, None, 0, None))
    if use_pallas:
        from repro.kernels.fused_superstep import ops as fused_ops

        def fn(values, deltas, tiles, nbr_ids, sel, msk, scales, overlay,
               pairs):
            del tiles, nbr_ids        # the pair view replaces ELL staging
            # the overlay must see the PRE-consumption deltas, gathered
            # before the kernel zeroes/infs them
            ride = overlay is not None and overlay.capacity
            if ride:
                consumed = _block_mask(sel, msk,
                                       values.shape[1])[None, :, None]
                if semiring == "plus_times":
                    raw = jnp.where(consumed, deltas, 0.0)
                    d_sel = (raw[:, sel, :] * scales[:, None, None]
                             * msk[None, :, None])          # [J, q, Vb]
                else:
                    d_sel = jnp.where(consumed, deltas, jnp.inf)[:, sel, :]
                    d_sel = jnp.where(msk[None, :, None] > 0, d_sel,
                                      jnp.inf)
            values, deltas = fused_ops.fused_push(
                values, deltas, pairs, sel, msk, scales, semiring=semiring)
            if ride:
                if semiring == "plus_times":
                    deltas = jax.vmap(
                        overlay_push_plus, in_axes=(0, 0, None, None))(
                            deltas, d_sel, overlay, sel)
                else:
                    values, deltas = jax.vmap(
                        overlay_push_min, in_axes=(0, 0, 0, None, None))(
                            values, deltas, d_sel, overlay, sel)
            return values, deltas

        return fn

    if semiring != "plus_times":
        def fn(values, deltas, tiles, nbr_ids, sel, msk, scales, overlay,
               pairs):
            del pairs
            return vm(values, deltas, tiles, nbr_ids, sel, msk, scales,
                      overlay)

        return fn

    def fn(values, deltas, tiles, nbr_ids, sel, msk, scales, overlay,
           pairs):
        if pairs is None:
            return vm(values, deltas, tiles, nbr_ids, sel, msk, scales,
                      overlay)
        bn = values.shape[1]
        selb = _block_mask(sel, msk, bn)[None, :, None]
        raw = jnp.where(selb, deltas, 0.0)
        d = raw * scales[:, None, None]
        base = deltas - raw
        contrib = jnp.einsum("jpv,pvw->jpw", d[:, pairs.src, :],
                             pairs.tiles)
        out = base.at[:, pairs.dst, :].add(contrib, mode="drop")
        values = values + raw
        deltas = out
        if overlay is not None and overlay.capacity:
            d_sel = d[:, sel, :] * msk[None, :, None]       # [J, q, Vb]
            deltas = jax.vmap(
                overlay_push_plus, in_axes=(0, 0, None, None))(
                    deltas, d_sel, overlay, sel)
        return values, deltas

    return fn


def indep_push_fn(push_one):
    """Per-job-selection push callable (un-jitted): each job its own [q];
    the overlay is still the shared view data (in_axes None)."""
    return jax.vmap(push_one, in_axes=(0, 0, None, None, 0, 0, 0, None))
