"""2D (jobs x blocks) mesh: shard the graph, not just the jobs.

repro.dist.graph replicates every view's adjacency on every device, so
the maximum graph is one device's memory — the opposite of the
production-scale north star.  This module adds the second mesh axis: the
BLOCK-ROW axis.  A (Dj x S) mesh composes the existing job-axis sharding
with a partition of the destination-sorted `BlockPairs` list into S
contiguous dst-ranges (NXgraph-style sub-shards):

  * block-shard s owns block rows [s*B_loc, (s+1)*B_loc) of every job's
    values/deltas AND the pair slice whose destinations fall there —
    pairs are dst-sorted, so the slice is contiguous and its first/last
    run flags stay valid (a dst run never spans shards);
  * adjacency TILES are therefore sharded too: each device holds ~P/S
    pair tiles instead of P, which is what lets a graph larger than one
    device's memory run at all (`benchmarks/run.py fig_graphscale`);
  * at each superstep the shards exchange only the FRONTIER — the
    consumed deltas of the <=q selected blocks, [J, q, Vb] — via a
    lax.psum (plus-times) / lax.pmin (min-plus) over the blocks axis
    inside the jitted superstep, so `steps_per_sync=inf` stays one host
    sync.  Each global block is owned by exactly one shard (non-owners
    contribute the semiring identity), so the collective is exact.
    `RunMetrics.halo_bytes` accounts this payload: occupied selection
    slots x Vb x itemsize x live jobs — proportional to frontier deltas,
    never to whole tiles.

Scheduling stays a single global two-level decision: per-(job, shard)
DO queues sample each shard's LOCAL blocks, are scatter-added into the
global [B_N] cumulative priority (psum over both axes — B_N floats of
queue metadata, not graph data), and `synthesize_topq` then computes the
same global queue on every device.  Fixpoints are bit-identical to the
single-device run for min-plus (min is exact and order-independent, and
d(u)+w is evaluated identically on whichever shard owns the
destination) and tolerance-tight for plus-times.

The frontier exchange can optionally be int8-compressed with error
feedback (`compress_halo=True`, plus-times shared-selection policies
only): the owner quantizes its rows against a per-(job, slot) scale,
non-owners contribute exact zeros, and the residual is carried on the
owned block rows and drained the next time the block is selected —
the same telescoping-bias construction as `dist.compression`.

Groups whose job axis does not divide the jobs axis, or whose B_N does
not divide the blocks axis, fall back to replication along that axis
(identical math, one-time `MeshLayoutWarning` naming the chosen layout).
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.algorithms.base import PLUS_TIMES
from repro.core import priority as prio
from repro.core.do_select import do_select_device
from repro.core.global_q import accumulate_priority, synthesize_topq
from repro.core.push import _block_mask
from repro.dist.compression import quantize_ef
from repro.obs.telemetry import device_buffers, device_write

JOBS_AXIS, BLOCKS_AXIS = "jobs", "blocks"

__all__ = [
    "Mesh2DSpec", "GroupLayout", "MeshLayoutWarning", "PairShards",
    "make_mesh2d", "partition_block_pairs", "shard_session_2d",
    "unshard_session", "build_device_step_2d", "run_device_2d",
    "shared_push_fn_2d", "indep_push_fn_2d", "reset_layout_warnings",
]


class MeshLayoutWarning(UserWarning):
    """A view group could not shard along a requested mesh axis and fell
    back to replication there (identical math, more memory/compute)."""


_LAYOUT_WARNED: set = set()


def reset_layout_warnings() -> None:
    """Forget which fallback layouts have been warned about (tests)."""
    _LAYOUT_WARNED.clear()


def warn_layout_once(view_key, axis_name: str, n_shard: int, size: int,
                     chosen: str) -> None:
    """One-time MeshLayoutWarning naming the layout actually chosen."""
    tag = (tuple(view_key), axis_name, n_shard, size, chosen)
    if tag in _LAYOUT_WARNED:
        return
    _LAYOUT_WARNED.add(tag)
    warnings.warn(
        f"view {view_key}: size {size} does not divide mesh axis "
        f"'{axis_name}' ({n_shard} shards) — falling back to layout "
        f"'{chosen}' (replicated along '{axis_name}'; identical math)",
        MeshLayoutWarning, stacklevel=3)


@dataclasses.dataclass(frozen=True)
class GroupLayout:
    """Per-view-group placement decision on a 2D mesh."""

    jobs_sharded: bool
    blocks_sharded: bool


@dataclasses.dataclass
class Mesh2DSpec:
    """A (jobs x blocks) mesh placement for a GraphSession.

    Held on the session as `sess._mesh2d`; its signature() feeds every
    jit-cache key so entering/leaving/re-entering a mesh re-uses — never
    grows — the one-entry-per-key compilation pins."""

    mesh: Mesh
    jobs_axis: str = JOBS_AXIS
    blocks_axis: str = BLOCKS_AXIS
    compress_halo: bool = False
    bits: int = 8

    @property
    def jobs_shards(self) -> int:
        return int(self.mesh.shape[self.jobs_axis])

    @property
    def block_shards(self) -> int:
        return int(self.mesh.shape[self.blocks_axis])

    def signature(self) -> tuple:
        return ("mesh2d", self.jobs_shards, self.block_shards,
                self.jobs_axis, self.blocks_axis, bool(self.compress_halo),
                int(self.bits))

    def layout(self, grp, warn: bool = False) -> GroupLayout:
        """Shard along an axis iff the group's extent divides it."""
        js = grp.capacity % self.jobs_shards == 0
        bs = grp.graph.num_blocks % self.block_shards == 0
        if warn and not js and self.jobs_shards > 1:
            warn_layout_once(grp.key, self.jobs_axis, self.jobs_shards,
                             grp.capacity, "jobs-replicated")
        if warn and not bs and self.block_shards > 1:
            warn_layout_once(grp.key, self.blocks_axis, self.block_shards,
                             grp.graph.num_blocks, "blocks-replicated")
        return GroupLayout(jobs_sharded=js, blocks_sharded=bs)

    def state_sharding(self, lay: GroupLayout) -> NamedSharding:
        ja = self.jobs_axis if lay.jobs_sharded else None
        ba = self.blocks_axis if lay.blocks_sharded else None
        return NamedSharding(self.mesh, P(ja, ba, None))

    def state_spec(self, lay: GroupLayout) -> P:
        return P(self.jobs_axis if lay.jobs_sharded else None,
                 self.blocks_axis if lay.blocks_sharded else None, None)

    def jobs_spec(self, lay: GroupLayout) -> P:
        return P(self.jobs_axis if lay.jobs_sharded else None)


def make_mesh2d(jobs: int = 1, blocks: int = 1, *,
                jobs_axis: str = JOBS_AXIS,
                blocks_axis: str = BLOCKS_AXIS) -> Mesh:
    """(jobs x blocks) mesh over the first jobs*blocks devices."""
    devs = jax.devices()
    n = jobs * blocks
    if not 1 <= n <= len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]).reshape(jobs, blocks),
                (jobs_axis, blocks_axis))


# ---------------------------------------------------------------------------
# PairShards: the dst-partitioned BlockPairs view
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PairShards:
    """`BlockPairs` partitioned into S contiguous dst-ranges.

    Pairs are destination-sorted, so shard s = dst // B_loc owns a
    contiguous slice; slicing preserves the first/last run flags (a dst
    run never spans shards).  Shards are padded to one common pair count
    with inert pairs: src 0, dst_local clamped to the shard's last real
    destination (pallas-safe), first/last 0, an all-`fill` tile — an
    exact no-op in both semirings.

      src        [S, Pm] int32  GLOBAL source block of each pair
      dst_local  [S, Pm] int32  destination block MINUS the shard offset
      first/last [S, Pm] int32  run flags, valid per shard
      tiles      [S, Pm, Vb, Vb] f32  the shard's pair tiles (the memory
                 that actually scales down 1/S — the capacity win)
      src_nnz    [B_N] int32  GLOBAL per-source real-pair counts (the
                 tile_pair_loads accounting is shard-agnostic)
    """

    num_shards: int
    pair_cap: int
    block_size: int
    num_blocks: int
    blocks_per_shard: int
    fill: float
    src: jnp.ndarray
    dst_local: jnp.ndarray
    first: jnp.ndarray
    last: jnp.ndarray
    tiles: jnp.ndarray
    src_nnz: jnp.ndarray

    def tree_flatten(self):
        leaves = (self.src, self.dst_local, self.first, self.last,
                  self.tiles, self.src_nnz)
        aux = (self.num_shards, self.pair_cap, self.block_size,
               self.num_blocks, self.blocks_per_shard, self.fill)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*aux, *leaves)


jax.tree_util.register_pytree_node(
    PairShards, PairShards.tree_flatten, PairShards.tree_unflatten)


def partition_block_pairs(bp, n_shards: int, fill: float) -> PairShards:
    """Split a dst-sorted `BlockPairs` into `n_shards` contiguous
    dst-range shards (requires num_blocks % n_shards == 0)."""
    bn, vb = bp.num_blocks, bp.block_size
    if bn % n_shards:
        raise ValueError(
            f"B_N={bn} does not divide into {n_shards} block shards")
    b_loc = bn // n_shards
    src, dst, first, last, tiles = map(
        np.asarray, jax.device_get((bp.src, bp.dst, bp.first, bp.last,
                                    bp.tiles)))
    bounds = np.searchsorted(dst, np.arange(n_shards + 1) * b_loc,
                             side="left")
    pm = max(1, int(np.max(np.diff(bounds))))
    s_src = np.zeros((n_shards, pm), np.int32)
    s_dst = np.zeros((n_shards, pm), np.int32)
    s_first = np.zeros((n_shards, pm), np.int32)
    s_last = np.zeros((n_shards, pm), np.int32)
    s_tiles = np.full((n_shards, pm, vb, vb), fill, np.float32)
    for s in range(n_shards):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        k = hi - lo
        if k:
            s_src[s, :k] = src[lo:hi]
            s_dst[s, :k] = dst[lo:hi] - s * b_loc
            s_dst[s, k:] = s_dst[s, k - 1]      # inert pads: clamp
            s_first[s, :k] = first[lo:hi]
            s_last[s, :k] = last[lo:hi]
            s_tiles[s, :k] = tiles[lo:hi]
    return PairShards(
        num_shards=n_shards, pair_cap=pm, block_size=vb, num_blocks=bn,
        blocks_per_shard=b_loc, fill=float(fill),
        src=jnp.asarray(s_src), dst_local=jnp.asarray(s_dst),
        first=jnp.asarray(s_first), last=jnp.asarray(s_last),
        tiles=jnp.asarray(s_tiles), src_nnz=bp.src_nnz)


def place_pair_shards(spec: Mesh2DSpec, ps: PairShards,
                      blocks_sharded: bool) -> PairShards:
    """device_put each leaf: pair slices along the blocks axis (or
    replicated for a blocks-replicated group), src_nnz replicated."""
    ba = spec.blocks_axis if blocks_sharded else None

    def put(x, spec_):
        return jax.device_put(x, NamedSharding(spec.mesh, spec_))

    return dataclasses.replace(
        ps,
        src=put(ps.src, P(ba)), dst_local=put(ps.dst_local, P(ba)),
        first=put(ps.first, P(ba)), last=put(ps.last, P(ba)),
        tiles=put(ps.tiles, P(ba)), src_nnz=put(ps.src_nnz, P()))


def pair_shards_spec(spec: Mesh2DSpec, blocks_sharded: bool) -> PairShards:
    """shard_map in/out spec pytree shaped like a PairShards."""
    ba = P(spec.blocks_axis) if blocks_sharded else P()
    return PairShards(
        num_shards=0, pair_cap=0, block_size=0, num_blocks=0,
        blocks_per_shard=0, fill=0.0,
        src=ba, dst_local=ba, first=ba, last=ba, tiles=ba, src_nnz=P())


# ---------------------------------------------------------------------------
# shard-local primitives (called inside shard_map)
# ---------------------------------------------------------------------------


def _sum_unique(x, lay: GroupLayout, ja: str, ba: str):
    """psum over both axes counting each logical contribution ONCE: a
    replicated axis gates all but index 0 before summing (the psum then
    re-broadcasts, so the result is replicated and uniform — safe to
    branch a while_loop on)."""
    g = x if lay.jobs_sharded else x * (
        jax.lax.axis_index(ja) == 0).astype(x.dtype)
    g = jax.lax.psum(g, ja)
    g2 = g if lay.blocks_sharded else g * (
        jax.lax.axis_index(ba) == 0).astype(g.dtype)
    return jax.lax.psum(g2, ba)


def _psum_blocks(x, lay: GroupLayout, ba: str):
    """Sum per-job quantities across block shards (gated when the group
    replicates blocks, so each block's contribution counts once)."""
    g = x if lay.blocks_sharded else x * (
        jax.lax.axis_index(ba) == 0).astype(x.dtype)
    return jax.lax.psum(g, ba)


def _exchange_shared(semiring: str, deltas, sel, msk, boff, b_loc: int,
                     bn: int, ba: str, lay: GroupLayout, err,
                     compress: bool, bits: int):
    """Consume the selected blocks' local deltas and exchange the
    frontier: every shard contributes its OWNED rows of the [J, q, Vb]
    selection (semiring identity elsewhere) and a psum/pmin over the
    blocks axis hands every shard the full frontier.  Returns
    (raw, base, d_sel, err) — raw the consumed local rows, base the
    post-consume local deltas, d_sel the exchanged [J, q, Vb] frontier
    (plus-times: UNSCALED; min-plus: inf on invalid slots), err the
    updated error-feedback residual (compress_halo only)."""
    selb = _block_mask(sel, msk, bn)                       # [B_N] global
    consumed = jax.lax.dynamic_slice_in_dim(selb, boff, b_loc)[None, :, None]
    lidx = jnp.clip(sel - boff, 0, b_loc - 1)
    owned = ((sel >= boff) & (sel < boff + b_loc) & (msk > 0))
    if semiring == PLUS_TIMES:  # noqa: RPA001 (static python arg)
        raw = jnp.where(consumed, deltas, 0.0)
        t = raw[:, lidx, :]                                # [J, q, Vb]
        if compress:  # noqa: RPA001 (static python arg)
            t = t + err[:, lidx, :]
            deq, res = quantize_ef(t, bits=bits, axis=-1)
            # drain the residual of re-selected owned rows; pads/unowned
            # slots scatter out of range and are dropped
            scatter_idx = jnp.where(owned, lidx, b_loc)
            err = err.at[:, scatter_idx, :].set(
                jnp.where(owned[None, :, None], res, 0.0), mode="drop")
            t = deq
        contrib = jnp.where(owned[None, :, None], t, 0.0)
        if lay.blocks_sharded:
            d_sel = jax.lax.psum(contrib, ba)
        else:   # every shard already holds the full rows
            d_sel = contrib
        base = deltas - raw
        return raw, base, d_sel, err
    raw = jnp.where(consumed, deltas, jnp.inf)
    t = raw[:, lidx, :]
    contrib = jnp.where(owned[None, :, None], t, jnp.inf)
    d_sel = jax.lax.pmin(contrib, ba) if lay.blocks_sharded else contrib
    d_sel = jnp.where(msk[None, :, None] > 0, d_sel, jnp.inf)
    base = jnp.where(consumed, jnp.inf, deltas)
    return raw, base, d_sel, err


def _exchange_indep(semiring: str, deltas, sel, msk, boff, b_loc: int,
                    bn: int, ba: str, lay: GroupLayout):
    """Per-job-selection analogue of `_exchange_shared` (sel/msk
    [J, q']); no compression — error feedback is defined per owned block
    row, which per-job consumption would make job-coupled."""
    j = deltas.shape[0]
    selb = jnp.zeros((j, bn), jnp.bool_)
    selb = selb.at[jnp.arange(j)[:, None], sel].max(msk > 0)
    consumed = jax.lax.dynamic_slice_in_dim(
        selb, boff, b_loc, axis=1)[:, :, None]
    lidx = jnp.clip(sel - boff, 0, b_loc - 1)              # [J, q']
    owned = ((sel >= boff) & (sel < boff + b_loc) & (msk > 0))
    if semiring == PLUS_TIMES:  # noqa: RPA001 (static python arg)
        raw = jnp.where(consumed, deltas, 0.0)
        t = jnp.take_along_axis(raw, lidx[:, :, None], axis=1)
        contrib = jnp.where(owned[:, :, None], t, 0.0)
        d_sel = jax.lax.psum(contrib, ba) if lay.blocks_sharded else contrib
        return raw, deltas - raw, d_sel
    raw = jnp.where(consumed, deltas, jnp.inf)
    t = jnp.take_along_axis(raw, lidx[:, :, None], axis=1)
    contrib = jnp.where(owned[:, :, None], t, jnp.inf)
    d_sel = jax.lax.pmin(contrib, ba) if lay.blocks_sharded else contrib
    d_sel = jnp.where(msk[:, :, None] > 0, d_sel, jnp.inf)
    return raw, jnp.where(consumed, jnp.inf, deltas), d_sel


def _widen(semiring: str, d_sel, sel, bn: int, shared: bool):
    """Scatter the exchanged [J, q', Vb] frontier into a [J, B_N, Vb]
    operand indexed by GLOBAL source block (what the pair sweep and the
    megakernel consume).  Padded slots alias block 0 with the identity,
    so they cannot re-push it."""
    j, _, vb = d_sel.shape
    if semiring == PLUS_TIMES:  # noqa: RPA001 (static python arg)
        wide = jnp.zeros((j, bn, vb), jnp.float32)
        if shared:  # noqa: RPA001 (static python arg)
            return wide.at[:, sel, :].add(d_sel)
        return wide.at[jnp.arange(j)[:, None], sel, :].add(d_sel)
    wide = jnp.full((j, bn, vb), jnp.inf, jnp.float32)
    if shared:  # noqa: RPA001 (static python arg)
        return wide.at[:, sel, :].min(d_sel)
    return wide.at[jnp.arange(j)[:, None], sel, :].min(d_sel)


def _min_candidates(d_wide, src, tiles):
    """[J, P, Vb] min-plus candidates: min over source rows v of
    d_wide[:, src, v] + tiles[:, v, :], folded per row to bound the
    temporary at [J, P, Vb] (no [J, P, Vb, Vb] broadcast)."""
    d_pair = d_wide[:, src, :]                             # [J, P, Vb]
    vb = tiles.shape[-1]

    def body(v, acc):
        return jnp.minimum(acc, d_pair[:, :, v, None] + tiles[None, :, v, :])

    init = jnp.full(d_pair.shape, jnp.inf, jnp.float32)
    return jax.lax.fori_loop(0, vb, body, init)


def _overlay_plus_local(deltas, d_sel, ov, sel, boff, b_loc: int,
                        shared: bool):
    """Scatter the selected blocks' overlay contributions into the LOCAL
    deltas: only entries whose destination vertex falls in this shard's
    rows land (others drop), so overlay updates route to owning shards."""
    if ov is None or ov.capacity == 0:
        return deltas
    vb = deltas.shape[-1]

    def one(d_j, dsel_j, sel_j):
        q = sel_j.shape[0]
        src_u, dst, w, mask = (ov.src_u[sel_j], ov.dst[sel_j], ov.w[sel_j],
                               ov.mask[sel_j])
        contrib = dsel_j[jnp.arange(q)[:, None], src_u] * w * mask
        ldst = dst - boff * vb
        ok = (ldst >= 0) & (ldst < b_loc * vb) & (mask > 0)
        ldst = jnp.where(ok, ldst, b_loc * vb)
        flat = d_j.reshape(-1)
        flat = flat.at[ldst.reshape(-1)].add(
            jnp.where(ok, contrib, 0.0).reshape(-1), mode="drop")
        return flat.reshape(b_loc, vb)

    in_axes = (0, 0, None) if shared else (0, 0, 0)
    return jax.vmap(one, in_axes=in_axes)(deltas, d_sel, sel)


def _overlay_min_local(values, d_sel, ov, sel, boff, b_loc: int,
                       shared: bool):
    """Scatter-min the selected blocks' overlay relaxations into the
    LOCAL values (improvement bookkeeping happens once, in the caller)."""
    if ov is None or ov.capacity == 0:
        return values
    vb = values.shape[-1]

    def one(v_j, dsel_j, sel_j):
        q = sel_j.shape[0]
        src_u, dst, w, mask = (ov.src_u[sel_j], ov.dst[sel_j], ov.w[sel_j],
                               ov.mask[sel_j])
        cand = jnp.where(mask > 0,
                         dsel_j[jnp.arange(q)[:, None], src_u] + w,
                         jnp.inf)
        ldst = dst - boff * vb
        ok = (ldst >= 0) & (ldst < b_loc * vb)
        ldst = jnp.where(ok, ldst, b_loc * vb)
        flat = v_j.reshape(-1)
        flat = flat.at[ldst.reshape(-1)].min(
            jnp.where(ok, cand, jnp.inf).reshape(-1), mode="drop")
        return flat.reshape(b_loc, vb)

    in_axes = (0, 0, None) if shared else (0, 0, 0)
    return jax.vmap(one, in_axes=in_axes)(values, d_sel, sel)


def _apply_pairs_local(semiring: str, values, deltas_base, raw, d_wide,
                       d_sel, sel, ps_src, ps_dstl, ps_first, ps_last,
                       ps_tiles, scales, msk, overlay, boff,
                       b_loc: int, shared: bool, use_pallas: bool):
    """One shard's pair run: push the exchanged frontier through the
    LOCAL dst-sorted pair slice (+ the overlay ride-along), with the
    one-shot improvement bookkeeping that is provably equivalent to the
    sequential per-block scan at every step (min is order-independent
    and `deltas[v] = min(base, new value)` iff any candidate improved).

    use_pallas sweeps the slice with the fused megakernel (per-shard
    pair run: global-src operand, local-dst output); otherwise the jnp
    einsum/scatter emulation."""
    if semiring == PLUS_TIMES:  # noqa: RPA001 (static python arg)
        d_push = d_wide * scales[:, None, None]
        if use_pallas:  # noqa: RPA001 (static python arg)
            from repro.kernels.fused_superstep.kernel import (
                fused_superstep_call)
            from repro.kernels.common import resolve_interpret
            from repro.kernels.fused_superstep.ops import _pick_job_block
            out = fused_superstep_call(
                ps_src, ps_dstl, ps_first, ps_last, d_push, deltas_base,
                ps_tiles, semiring=semiring,
                job_block=_pick_job_block(values.shape[0],
                                          values.shape[-1], semiring),
                interpret=resolve_interpret(None))
        else:
            contrib = jnp.einsum("jpv,pvw->jpw", d_push[:, ps_src, :],
                                 ps_tiles)
            out = deltas_base.at[:, ps_dstl, :].add(contrib)
        d_ov = (d_sel * scales[:, None, None]
                * (msk[None, :, None] if shared else msk[:, :, None]))
        out = _overlay_plus_local(out, d_ov, overlay, sel, boff, b_loc,
                                  shared)
        return values + raw, out
    # min-plus
    if use_pallas:  # noqa: RPA001 (static python arg)
        from repro.kernels.fused_superstep.kernel import fused_superstep_call
        from repro.kernels.common import resolve_interpret
        from repro.kernels.fused_superstep.ops import _pick_job_block
        v1, d1 = fused_superstep_call(
            ps_src, ps_dstl, ps_first, ps_last, d_wide, deltas_base,
            ps_tiles, values=values, semiring=semiring,
            job_block=_pick_job_block(values.shape[0], values.shape[-1],
                                      semiring),
            interpret=resolve_interpret(None))
        v2 = _overlay_min_local(v1, d_sel, overlay, sel, boff, b_loc, shared)
        improved = v2 < v1
        return v2, jnp.minimum(d1, jnp.where(improved, v2, jnp.inf))
    cand = _min_candidates(d_wide, ps_src, ps_tiles)
    v_old = values
    v1 = values.at[:, ps_dstl, :].min(cand)
    v2 = _overlay_min_local(v1, d_sel, overlay, sel, boff, b_loc, shared)
    improved = v2 < v_old
    return v2, jnp.minimum(deltas_base, jnp.where(improved, v2, jnp.inf))


# ---------------------------------------------------------------------------
# 2D device superstep: both scheduling levels + push + exchange, jitted
# ---------------------------------------------------------------------------


def _sum_jobs(x, lay: GroupLayout, ja: str):
    """Sum a per-jobs-shard quantity across the jobs axis, counting each
    job once (gated when the group replicates jobs)."""
    g = x if lay.jobs_sharded else x * (
        jax.lax.axis_index(ja) == 0).astype(x.dtype)
    return jax.lax.psum(g, ja)


def build_device_step_2d(policy, sess, spec: Mesh2DSpec):
    """Compile the session's superstep for `policy` on the 2D mesh.

    The same contract as `core.policy.build_device_step` — one jitted
    callable, finite steps_per_sync scans / inf while_loops — but the
    whole step body runs INSIDE a shard_map over (jobs x blocks): DO
    sampling per (job, block-shard) over local blocks, global-queue
    synthesis from the psum'd [B_N] cumulative priority, the frontier
    exchange, and each shard's pair run.  The carry grows two slots over
    the 1D layout: state[9] accumulates `halo_bytes` and state[10] is
    the per-group error-feedback residual (all-zero placeholders unless
    compress_halo applies to the group).  Cache via
    session._device_step_fn, whose key carries spec.signature()."""
    from repro.core.policy import AllBlocks, Independent, TwoLevel
    groups = sess.view_groups()
    n_groups = len(groups)
    algs = [g.alg for g in groups]
    lays = [spec.layout(g, warn=True) for g in groups]
    ja, ba = spec.jobs_axis, spec.blocks_axis
    dj, s_blk = spec.jobs_shards, spec.block_shards
    q = int(sess.q)
    alpha = float(sess.alpha)
    samples = int(sess.samples)
    bn = int(sess.scheduler.num_blocks)
    k_sync = policy.steps_per_sync
    needs_pairs = policy.needs_pairs
    tel_cfg = getattr(sess, "telemetry", None)
    tel_cap = int(tel_cfg.capacity) if tel_cfg is not None else 0
    use_pallas = bool(sess.use_pallas)

    if isinstance(policy, Independent):
        mode = "indep"
    elif isinstance(policy, AllBlocks):
        mode = "all"
    elif isinstance(policy, TwoLevel):
        mode = "two"
    else:
        raise NotImplementedError(
            f"policy {type(policy).__name__} has no 2D-mesh device path — "
            "run it on the host backend or a 1D jobs mesh")

    b_locs = [bn // s_blk if lay.blocks_sharded else bn for lay in lays]
    j_locs = [g.capacity // dj if lay.jobs_sharded else g.capacity
              for g, lay in zip(groups, lays)]
    vbs = [int(g.graph.block_size) for g in groups]
    compress = [spec.compress_halo and g.semiring == PLUS_TIMES
                and mode != "indep" and lay.blocks_sharded
                for g, lay in zip(groups, lays)]
    any_bs = any(lay.blocks_sharded for lay in lays) and s_blk > 1

    def _boff(gi):
        if lays[gi].blocks_sharded:
            return jax.lax.axis_index(ba) * b_locs[gi]
        return jnp.int32(0)

    def _group_sample(nu, pm, key, gi):
        """Per-(job, shard) DO queues over this shard's local blocks."""
        lay = lays[gi]
        kb = jax.random.fold_in(
            jax.random.fold_in(key, gi),
            jax.lax.axis_index(ba) if lay.blocks_sharded else 0)
        joff = (jax.lax.axis_index(ja) * j_locs[gi] if lay.jobs_sharded
                else jnp.int32(0))
        jids = joff + jnp.arange(nu.shape[0], dtype=jnp.int32)
        keys = jax.vmap(lambda t: jax.random.fold_in(kb, t))(jids)
        return jax.vmap(
            lambda n, p, k: do_select_device(n, p, q, k, samples))(
                nu, pm, keys)

    def unconverged_total(vs, ds):
        tot = jnp.float32(0)
        for gi in range(n_groups):
            loc = jnp.sum(
                algs[gi].unconverged(vs[gi], ds[gi]).astype(jnp.float32))
            tot = tot + _sum_unique(loc, lays[gi], ja, ba)
        return tot.astype(jnp.int32)

    def superstep(carry, scales, tiles, nbrs, ovs, prs, key):
        (it, vs, ds, loads, pushes, pair_loads, iters, boost, tel, halo,
         errs) = carry
        kstep = jax.random.fold_in(key, it)
        node_uns, p_means, actives, n_lives, keeps = [], [], [], [], []
        for gi in range(n_groups):
            lay = lays[gi]
            if needs_pairs:
                nu, pm = compute_pairs_local(algs[gi], vs[gi], ds[gi])
                if lay.blocks_sharded:
                    bsl = jax.lax.dynamic_slice_in_dim(
                        boost, _boff(gi), b_locs[gi])
                else:
                    bsl = boost
                pm = pm + bsl[None, :] * (nu > 0)
            else:
                un = algs[gi].unconverged(vs[gi], ds[gi])
                nu = jnp.sum(un, axis=-1).astype(jnp.float32)
                pm = None
            cnt = _psum_blocks(prio.counts_from_pairs(nu).astype(jnp.float32),
                               lay, ba)
            act = cnt > 0
            n_live = _sum_jobs(jnp.sum(act.astype(jnp.float32)), lay, ja)
            node_uns.append(nu)
            p_means.append(pm)
            actives.append(act)
            n_lives.append(n_live)
            keeps.append(n_live > 0)

        # -- selection ----------------------------------------------------
        sel_pushes = jnp.float32(0)
        if mode == "two":
            pri = jnp.zeros((bn,), jnp.float32)
            heads_f = jnp.zeros((bn,), jnp.float32)
            for gi in range(n_groups):
                sel, msk = _group_sample(node_uns[gi], p_means[gi], kstep, gi)
                selg = sel + _boff(gi)
                pri_l = jnp.zeros((bn,), jnp.float32)
                heads_l = jnp.zeros((bn,), jnp.bool_)
                pri_l, heads_l = accumulate_priority(pri_l, heads_l, selg,
                                                     msk, q)
                pri = pri + _sum_unique(pri_l, lays[gi], ja, ba)
                heads_f = heads_f + _sum_unique(
                    heads_l.astype(jnp.float32), lays[gi], ja, ba)
            gsel, gmsk = synthesize_topq(pri, heads_f > 0, q, alpha)
            tile_loads = jnp.sum(gmsk > 0).astype(jnp.float32)
            for gi in range(n_groups):
                lsel = jnp.clip(gsel - _boff(gi), 0, b_locs[gi] - 1)
                own = ((gsel >= _boff(gi))
                       & (gsel < _boff(gi) + b_locs[gi]) & (gmsk > 0))
                cnt = jnp.sum(((node_uns[gi][:, lsel] > 0)
                               & own[None, :]).astype(jnp.float32))
                sel_pushes = sel_pushes + _sum_unique(cnt, lays[gi], ja, ba)
            sels = [gsel] * n_groups
            msks = [gmsk] * n_groups
            shared = True
        elif mode == "all":
            gsel = jnp.arange(bn, dtype=jnp.int32)
            gmsk = jnp.ones(bn, jnp.float32)
            tile_loads = jnp.float32(bn)
            sel_pushes = jnp.float32(bn) * sum(n_lives)
            sels = [gsel] * n_groups
            msks = [gmsk] * n_groups
            shared = True
        else:   # indep
            sels, msks = [], []
            tile_loads = jnp.float32(0)
            for gi in range(n_groups):
                sel, msk = _group_sample(node_uns[gi], p_means[gi], kstep, gi)
                selg = sel + _boff(gi)
                if lays[gi].blocks_sharded:
                    sg = jax.lax.all_gather(selg, ba)       # [S, J_loc, q]
                    mg = jax.lax.all_gather(msk, ba)
                    selg = jnp.moveaxis(sg, 0, 1).reshape(sel.shape[0], -1)
                    msk = jnp.moveaxis(mg, 0, 1).reshape(sel.shape[0], -1)
                sels.append(selg)
                msks.append(msk)
                tile_loads = tile_loads + _sum_jobs(
                    jnp.sum(msk > 0).astype(jnp.float32), lays[gi], ja)
            sel_pushes = tile_loads
            shared = False

        # -- exchange + per-shard pair runs --------------------------------
        new_vs, new_ds, new_iters, new_errs = [], [], [], []
        pair_step = jnp.float32(0)
        halo_step = jnp.float32(0)
        for gi in range(n_groups):
            g, lay = groups[gi], lays[gi]
            boff, b_loc, vb = _boff(gi), b_locs[gi], vbs[gi]
            sel, msk = sels[gi], msks[gi]
            if shared:
                raw, base, d_sel, err2 = _exchange_shared(
                    g.semiring, ds[gi], sel, msk, boff, b_loc, bn, ba, lay,
                    errs[gi], compress[gi], spec.bits)
                pair_cnt = jnp.sum(prs[gi].src_nnz[sel]
                                   * (msk > 0)).astype(jnp.float32)
                occ_g = jnp.sum(msk > 0).astype(jnp.float32)
            else:
                raw, base, d_sel = _exchange_indep(
                    g.semiring, ds[gi], sel, msk, boff, b_loc, bn, ba, lay)
                err2 = errs[gi]
                cnt = jnp.sum(prs[gi].src_nnz[sel]
                              * (msk > 0)).astype(jnp.float32)
                pair_cnt = _sum_jobs(cnt, lay, ja)
                occ_g = _sum_jobs(jnp.sum(msk > 0).astype(jnp.float32),
                                  lay, ja)
            d_wide = _widen(g.semiring, d_sel, sel, bn, shared)
            v2, d2 = _apply_pairs_local(
                g.semiring, vs[gi], base, raw, d_wide, d_sel, sel,
                prs[gi].src[0], prs[gi].dst_local[0], prs[gi].first[0],
                prs[gi].last[0], prs[gi].tiles[0], scales[gi], msk, ovs[gi],
                boff, b_loc, shared, use_pallas)
            keep = keeps[gi]
            new_vs.append(jnp.where(keep, v2, vs[gi]))
            new_ds.append(jnp.where(keep, d2, ds[gi]))
            new_errs.append(jnp.where(keep, err2, errs[gi])
                            if compress[gi] else errs[gi])
            new_iters.append(iters[gi] + actives[gi].astype(jnp.int32))
            pair_step = pair_step + keep.astype(jnp.float32) * pair_cnt
            if lay.blocks_sharded and s_blk > 1:
                itemb = 1.0 if (compress[gi] and shared) else 4.0
                if shared:
                    payload = occ_g * vb * itemb * n_lives[gi]
                else:
                    payload = occ_g * vb * 4.0
                halo_step = halo_step + keep.astype(jnp.float32) * payload
        if mode == "two" and any_bs:
            halo_step = halo_step + 8.0 * bn   # [B_N] pri + head psum
        if tel_cap:
            # written AFTER the exchange loop so the row carries the
            # superstep's real pair/halo traffic alongside the pre-push
            # scheduling reads
            idx = jnp.minimum(it, tel_cap - 1)
            occ = (jnp.sum(msks[0] > 0).astype(jnp.int32) if shared
                   else tile_loads.astype(jnp.int32))
            tel = device_write(
                tel, idx,
                sum(n_lives).astype(jnp.int32),
                tile_loads.astype(jnp.int32),
                sel_pushes.astype(jnp.int32), occ,
                jnp.sum(boost > 0).astype(jnp.int32),
                jnp.stack([_sum_unique(jnp.sum(node_uns[gi]), lays[gi],
                                       ja, ba).astype(jnp.int32)
                           for gi in range(n_groups)]),
                jnp.stack([jax.lax.pmax(jax.lax.pmax(
                    jnp.max(algs[gi].vertex_priority(vs[gi], ds[gi])), ja),
                    ba) for gi in range(n_groups)]),
                tile_pair_loads=pair_step.astype(jnp.int32),
                halo_bytes=halo_step)
        return (it + 1, tuple(new_vs), tuple(new_ds),
                loads + tile_loads, pushes + sel_pushes,
                pair_loads + pair_step, tuple(new_iters),
                jnp.zeros_like(boost), tel, halo + halo_step,
                tuple(new_errs))

    def local_step(state, scales, tiles, nbrs, ovs, prs, max_steps, key):
        del tiles, nbrs   # the pair slices replace block-ELL staging

        def body(c):
            return superstep(c, scales, None, None, ovs, prs, key)

        def live(c):
            return (unconverged_total(c[1], c[2]) > 0) & (c[0] < max_steps)

        if k_sync == math.inf:
            state = jax.lax.while_loop(live, body, state)
        else:
            def gated(c, _):
                return jax.lax.cond(live(c), body, lambda x: x, c), None
            state, _ = jax.lax.scan(gated, state, None, length=int(k_sync))
        return state, unconverged_total(state[1], state[2])

    # ---- shard_map wiring -------------------------------------------------
    vs_specs = tuple(spec.state_spec(lay) for lay in lays)
    iters_specs = tuple(spec.jobs_spec(lay) for lay in lays)
    err_specs = tuple(spec.state_spec(lays[gi]) if compress[gi] else P()
                      for gi in range(n_groups))
    tel_spec = (tuple(P() for _ in device_buffers(1, n_groups))
                if tel_cap else ())
    state_spec = (P(), vs_specs, vs_specs, P(), P(), P(), iters_specs,
                  P(), tel_spec, P(), err_specs)
    graph_specs = tuple(
        P(ba) if lay.blocks_sharded else P() for lay in lays)
    ovs_specs = tuple(
        dataclasses.replace(g.overlay, src_u=P(), dst=P(), w=P(), mask=P())
        for g in groups)
    # spec pytrees must carry the SAME aux as the arguments they match
    prs_specs = []
    for g, lay in zip(groups, lays):
        bsp = P(ba) if lay.blocks_sharded else P()
        prs_specs.append(dataclasses.replace(
            sess._pair_shards(g), src=bsp, dst_local=bsp, first=bsp,
            last=bsp, tiles=bsp, src_nnz=P()))
    prs_specs = tuple(prs_specs)
    scales_specs = tuple(spec.jobs_spec(lay) for lay in lays)
    in_specs = (state_spec, scales_specs, graph_specs, graph_specs,
                ovs_specs, prs_specs, P(), P())
    return jax.jit(jax.shard_map(
        local_step, mesh=spec.mesh, in_specs=in_specs,
        out_specs=(state_spec, P()), check_vma=False))


def compute_pairs_local(alg, values, deltas):
    """<Node_un, P_mean> of the LOCAL block rows ([J_loc, B_loc, Vb] in,
    [J_loc, B_loc] out) — `core.push.compute_pairs` is already
    shard-local (per-vertex priority, per-block reduce)."""
    from repro.core.push import compute_pairs
    return compute_pairs(alg, values, deltas)


def run_device_2d(policy, sess, max_supersteps: int):
    """2D-mesh device driver: `core.policy._run_device` with the carry's
    two extra slots (halo_bytes accumulator, error-feedback residuals).
    Sampling streams, chunking semantics and the dtype contract are
    identical to the 1D driver."""
    from repro.core.policy import RunMetrics
    from repro.obs.telemetry import series_from_device
    spec = sess._mesh2d
    groups = sess.view_groups()
    lays = [spec.layout(g) for g in groups]
    step_fn = sess._device_step_fn(policy)
    boost = sess._consume_dirty_boost()
    bn = sess.scheduler.num_blocks
    tel_cfg = getattr(sess, "telemetry", None)
    tel_cap = int(tel_cfg.capacity) if tel_cfg is not None else 0
    trace = getattr(sess, "trace", None)
    trace = trace if trace is not None and trace.enabled else None
    compress = [spec.compress_halo and g.semiring == PLUS_TIMES
                and not _policy_is_indep(policy) and lay.blocks_sharded
                for g, lay in zip(groups, lays)]
    errs = tuple(
        jax.device_put(jnp.zeros_like(g.deltas), spec.state_sharding(lay))
        if comp else jnp.zeros((1, 1, 1), jnp.float32)
        for g, lay, comp in zip(groups, lays, compress))
    state = (jnp.int32(0),
             tuple(g.values for g in groups),
             tuple(g.deltas for g in groups),
             jnp.float32(0), jnp.float32(0), jnp.float32(0),
             tuple(jnp.zeros(g.capacity, jnp.int32) for g in groups),
             jnp.zeros(bn, jnp.float32) if boost is None
             else jnp.asarray(boost, jnp.float32),
             device_buffers(tel_cap, len(groups)) if tel_cap else (),
             jnp.float32(0), errs)
    scales = tuple(g.push_scale for g in groups)
    tiles = tuple(g.graph.tiles for g in groups)
    nbrs = tuple(g.graph.nbr_ids for g in groups)
    ovs = tuple(g.overlay for g in groups)
    prs = tuple(sess._pair_shards(g) for g in groups)
    budget = int(min(max_supersteps, np.iinfo(np.int32).max))
    max_steps = jnp.int32(budget)
    key = jax.random.fold_in(jax.random.PRNGKey(sess.seed),
                             sess.scheduler._step)
    m = RunMetrics()
    while True:
        t_chunk = trace.now_us() if trace else 0.0
        state, un = step_fn(state, scales, tiles, nbrs, ovs, prs,
                            max_steps, key)
        it_h, un_h = map(int, jax.device_get((state[0], un)))
        m.host_syncs += 1
        if trace:
            trace.complete("device_chunk", t_chunk,
                           trace.now_us() - t_chunk, cat="superstep", tid=2,
                           sync=m.host_syncs - 1, supersteps_done=it_h)
        if un_h == 0 or it_h >= budget:
            break
    sess.scheduler._step += it_h
    for gi, g in enumerate(groups):
        g.values, g.deltas = state[1][gi], state[2][gi]
    m.supersteps = it_h
    loads_h, pushes_h, pair_loads_h, iters_h, halo_h = jax.device_get(
        (state[3], state[4], state[5], state[6], state[9]))
    m.tile_loads = int(loads_h)
    m.job_block_pushes = int(pushes_h)
    m.tile_pair_loads = int(pair_loads_h)
    m.halo_bytes = float(halo_h)
    m.converged = un_h == 0
    m.iterations_per_job = np.concatenate(
        [np.asarray(x, dtype=np.int64) for x in iters_h])
    if tel_cap:
        m.telemetry = series_from_device(state[8], it_h,
                                         [g.key for g in groups])
    return m


def _policy_is_indep(policy) -> bool:
    from repro.core.policy import Independent
    return isinstance(policy, Independent)


# ---------------------------------------------------------------------------
# host-backend push functions (scheduling on host, 2D push on device)
# ---------------------------------------------------------------------------


def shared_push_fn_2d(spec: Mesh2DSpec, grp, use_pallas: bool):
    """2D replacement for `core.push.shared_push_fn`: same 9-arg
    signature with `pairs` a `PairShards`; the jitted shard_map consumes
    the host scheduler's global [q] selection, exchanges the frontier
    and runs each shard's pair slice.  The host scheduler sees GLOBAL
    state, so the schedule — and for min-plus the fixpoint, bit-for-bit
    — matches the unsharded session.  Variants are cached per (overlay
    capacity, pair shape) because both are part of the traced program's
    pytree structure."""
    lay = spec.layout(grp, warn=True)
    semiring = grp.semiring
    bn = int(grp.graph.num_blocks)
    b_loc = bn // spec.block_shards if lay.blocks_sharded else bn
    ja, ba = spec.jobs_axis, spec.blocks_axis
    variants = {}

    def build(ov_cap: int, ps_aux: tuple):
        def local(values, deltas, sel, msk, scales, overlay, ps):
            boff = (jax.lax.axis_index(ba) * b_loc if lay.blocks_sharded
                    else jnp.int32(0))
            raw, base, d_sel, _ = _exchange_shared(
                semiring, deltas, sel, msk, boff, b_loc, bn, ba, lay,
                None, False, 8)
            d_wide = _widen(semiring, d_sel, sel, bn, True)
            return _apply_pairs_local(
                semiring, values, base, raw, d_wide, d_sel, sel,
                ps.src[0], ps.dst_local[0], ps.first[0], ps.last[0],
                ps.tiles[0], scales, msk, overlay, boff, b_loc, True,
                use_pallas)

        st = spec.state_spec(lay)
        ov_spec = TileOverlaySpec(ov_cap)
        ps_spec = pair_shards_spec(spec, lay.blocks_sharded)
        ps_spec = dataclasses.replace(
            ps_spec, num_shards=ps_aux[0], pair_cap=ps_aux[1],
            block_size=ps_aux[2], num_blocks=ps_aux[3],
            blocks_per_shard=ps_aux[4], fill=ps_aux[5])
        return jax.jit(jax.shard_map(
            local, mesh=spec.mesh,
            in_specs=(st, st, P(), P(), spec.jobs_spec(lay), ov_spec,
                      ps_spec),
            out_specs=(st, st), check_vma=False))

    def fn(values, deltas, tiles, nbr_ids, sel, msk, scales, overlay,
           pairs):
        del tiles, nbr_ids
        ps_aux = pairs.tree_flatten()[1]
        k = (overlay.capacity if overlay is not None else 0, ps_aux)
        if k not in variants:
            variants[k] = build(k[0], ps_aux)
        return variants[k](values, deltas, sel, msk, scales, overlay,
                           pairs)

    return fn


def indep_push_fn_2d(spec: Mesh2DSpec, grp):
    """2D replacement for `core.push.indep_push_fn` (per-job [J, q]
    selections; one extra trailing `pairs` argument the 2D host driver
    supplies)."""
    lay = spec.layout(grp, warn=True)
    semiring = grp.semiring
    bn = int(grp.graph.num_blocks)
    b_loc = bn // spec.block_shards if lay.blocks_sharded else bn
    ja, ba = spec.jobs_axis, spec.blocks_axis
    variants = {}

    def build(ov_cap: int, ps_aux: tuple):
        def local(values, deltas, sel, msk, scales, overlay, ps):
            boff = (jax.lax.axis_index(ba) * b_loc if lay.blocks_sharded
                    else jnp.int32(0))
            raw, base, d_sel = _exchange_indep(
                semiring, deltas, sel, msk, boff, b_loc, bn, ba, lay)
            d_wide = _widen(semiring, d_sel, sel, bn, False)
            return _apply_pairs_local(
                semiring, values, base, raw, d_wide, d_sel, sel,
                ps.src[0], ps.dst_local[0], ps.first[0], ps.last[0],
                ps.tiles[0], scales, msk, overlay, boff, b_loc, False,
                False)

        st = spec.state_spec(lay)
        jsp = spec.jobs_spec(lay)
        ov_spec = TileOverlaySpec(ov_cap)
        ps_spec = pair_shards_spec(spec, lay.blocks_sharded)
        ps_spec = dataclasses.replace(
            ps_spec, num_shards=ps_aux[0], pair_cap=ps_aux[1],
            block_size=ps_aux[2], num_blocks=ps_aux[3],
            blocks_per_shard=ps_aux[4], fill=ps_aux[5])
        return jax.jit(jax.shard_map(
            local, mesh=spec.mesh,
            in_specs=(st, st, jsp, jsp, jsp, ov_spec, ps_spec),
            out_specs=(st, st), check_vma=False))

    def fn(values, deltas, tiles, nbr_ids, sel, msk, scales, overlay,
           pairs):
        del tiles, nbr_ids
        ps_aux = pairs.tree_flatten()[1]
        k = (overlay.capacity if overlay is not None else 0, ps_aux)
        if k not in variants:
            variants[k] = build(k[0], ps_aux)
        return variants[k](values, deltas, sel, msk, scales, overlay,
                           pairs)

    return fn


def TileOverlaySpec(capacity: int):
    """shard_map spec pytree shaped like a (replicated) TileOverlay."""
    from repro.graph.structure import TileOverlay
    return TileOverlay(capacity=capacity, src_u=P(), dst=P(), w=P(),
                       mask=P())


def host_halo_bytes(spec: Mesh2DSpec, groups, selection, actives) -> float:
    """Frontier payload of one HOST-driver superstep (see module doc):
    occupied selection slots x Vb x 4 bytes x live jobs, summed over the
    blocks-sharded groups that were pushed."""
    if spec is None or spec.block_shards <= 1:
        return 0.0
    total = 0.0
    for gi, (grp, act) in enumerate(zip(groups, actives)):
        if not act.any() or not spec.layout(grp).blocks_sharded:
            continue
        vb = int(grp.graph.block_size)
        if selection.shared:
            occ = float(np.sum(np.asarray(selection.msk) > 0))
            total += occ * vb * 4.0 * float(act.sum())
        else:
            total += float(np.sum(np.asarray(selection.msk[gi]) > 0)) \
                * vb * 4.0
    return total


# ---------------------------------------------------------------------------
# session placement
# ---------------------------------------------------------------------------


def shard_session_2d(mesh: Mesh, session, axes=(JOBS_AXIS, BLOCKS_AXIS),
                     compress_halo: bool = False, bits: int = 8):
    """Place a GraphSession on a 2D (jobs x blocks) mesh.

    Job state shards over BOTH axes (rows of blocks to the owning block
    shard), adjacency tiles / neighbour ids shard their leading block
    dim over the blocks axis, overlays and masks replicate (shared view
    data staged alongside the owning shard's tiles; dirty-block boosts
    broadcast).  Records the placement as `session._mesh2d`, which
    reroutes the device superstep and the host push functions through
    this module until `unshard_session`."""
    ja, ba = axes
    if ja not in mesh.axis_names or ba not in mesh.axis_names:
        raise ValueError(
            f"mesh axes {mesh.axis_names} do not include {axes}")
    spec = Mesh2DSpec(mesh, ja, ba, compress_halo=compress_halo, bits=bits)
    for grp in session.view_groups():
        lay = spec.layout(grp, warn=True)
        sh3 = spec.state_sharding(lay)
        grp.values = jax.device_put(grp.values, sh3)
        grp.deltas = jax.device_put(grp.deltas, sh3)
        grp.push_scale = jax.device_put(
            grp.push_scale, NamedSharding(mesh, spec.jobs_spec(lay)))
        gsh = P(ba) if lay.blocks_sharded else P()
        g = grp.graph
        g.tiles = jax.device_put(g.tiles, NamedSharding(mesh, gsh))
        g.nbr_ids = jax.device_put(g.nbr_ids, NamedSharding(mesh, gsh))
        g.nbr_mask = jax.device_put(g.nbr_mask, NamedSharding(mesh, gsh))
        g.vertex_mask = jax.device_put(g.vertex_mask,
                                       NamedSharding(mesh, P()))
        if grp.overlay is not None:
            grp.overlay = dataclasses.replace(
                grp.overlay,
                src_u=jax.device_put(grp.overlay.src_u,
                                     NamedSharding(mesh, P())),
                dst=jax.device_put(grp.overlay.dst,
                                   NamedSharding(mesh, P())),
                w=jax.device_put(grp.overlay.w, NamedSharding(mesh, P())),
                mask=jax.device_put(grp.overlay.mask,
                                    NamedSharding(mesh, P())))
        grp.pair_shards = None      # rebuild lazily against this placement
    session._mesh2d = spec
    return session


def unshard_session(session):
    """Gather every view group back to single-device placement and clear
    the 2D-mesh routing (the inverse of `shard_session_2d`)."""
    spec = getattr(session, "_mesh2d", None)
    if spec is None:
        return session
    for grp in session.view_groups():
        grp.values = jnp.asarray(jax.device_get(grp.values))
        grp.deltas = jnp.asarray(jax.device_get(grp.deltas))
        grp.push_scale = jnp.asarray(jax.device_get(grp.push_scale))
        g = grp.graph
        g.tiles = jnp.asarray(jax.device_get(g.tiles))
        g.nbr_ids = jnp.asarray(jax.device_get(g.nbr_ids))
        g.nbr_mask = jnp.asarray(jax.device_get(g.nbr_mask))
        g.vertex_mask = jnp.asarray(jax.device_get(g.vertex_mask))
        if grp.overlay is not None:
            grp.overlay = dataclasses.replace(
                grp.overlay,
                src_u=jnp.asarray(jax.device_get(grp.overlay.src_u)),
                dst=jnp.asarray(jax.device_get(grp.overlay.dst)),
                w=jnp.asarray(jax.device_get(grp.overlay.w)),
                mask=jnp.asarray(jax.device_get(grp.overlay.mask)))
        grp.pair_shards = None
    session._mesh2d = None
    return session
