"""Graph structures: host CSR + device block-ELL dense tiles.

The paper schedules graph data in *blocks* ("a block can be placed in the
Cache", §3).  On TPU the cache is VMEM, and the natural VMEM-resident unit is
a dense [Vb, Vb] adjacency tile (MXU-friendly), stored block-sparse: for each
source block we keep up to K neighbouring destination blocks (block-ELL).

tiles[b, k, u, v] = weight of edge  (b*Vb + u)  ->  (nbr_ids[b, k]*Vb + v)
with `fill` (0.0 for plus-times, +inf for min-plus) where no edge exists.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax.numpy as jnp


@dataclasses.dataclass
class CSRGraph:
    """Host-side CSR over out-edges (numpy)."""

    n: int
    indptr: np.ndarray  # [n+1] int64
    indices: np.ndarray  # [nnz] int32 destination vertex
    weights: np.ndarray  # [nnz] float32

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    @property
    def out_degree(self) -> np.ndarray:
        return np.diff(self.indptr).astype(np.int32)

    @staticmethod
    def from_edges(n: int, src: np.ndarray, dst: np.ndarray,
                   weights: Optional[np.ndarray] = None) -> "CSRGraph":
        """Build CSR from an edge list; duplicate edges keep the min weight.

        Accepts any array-like input (lists, float arrays) and the empty
        edge list — evolving-graph mutation batches produce both (a batch
        of pure deletions leaves rows empty), so these are first-class
        inputs, not error cases.  The min-weight dedupe is idempotent:
        re-applying a batch that re-inserts an existing edge with a higher
        weight never raises the stored weight.
        """
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if weights is None:
            weights = np.ones(len(src), dtype=np.float32)
        weights = np.asarray(weights, dtype=np.float32)
        if not (len(src) == len(dst) == len(weights)):
            raise ValueError(
                f"ragged edge list: {len(src)}/{len(dst)}/{len(weights)}")
        if len(src) and (src.min() < 0 or src.max() >= n
                         or dst.min() < 0 or dst.max() >= n):
            raise ValueError(f"edge endpoints out of range for n={n}")
        # dedupe (src, dst), keep min weight (matters for SSSP correctness)
        key = src * n + dst
        order = np.lexsort((weights, key))
        key, src, dst, weights = key[order], src[order], dst[order], weights[order]
        keep = np.ones(len(key), dtype=bool)
        keep[1:] = key[1:] != key[:-1]
        src, dst, weights = src[keep], dst[keep], weights[keep]
        counts = np.bincount(src, minlength=n)
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        return CSRGraph(n=n, indptr=indptr, indices=dst.astype(np.int32),
                        weights=weights.astype(np.float32))

    def symmetrized(self) -> "CSRGraph":
        """Union of edges and reverse edges (for WCC-style algorithms).

        Antiparallel pairs (u->v and v->u) collapse to min weight on both
        directions (from_edges dedupe), so the result is a valid weighted
        undirected graph even after asymmetric reweights."""
        src = np.repeat(np.arange(self.n, dtype=np.int32), self.out_degree)
        all_src = np.concatenate([src, self.indices])
        all_dst = np.concatenate([self.indices, src])
        all_w = np.concatenate([self.weights, self.weights])
        return CSRGraph.from_edges(self.n, all_src, all_dst, all_w)

    def row(self, u: int) -> tuple:
        """(dst indices, weights) of u's out-row, dst-ascending."""
        lo, hi = int(self.indptr[u]), int(self.indptr[u + 1])
        return self.indices[lo:hi], self.weights[lo:hi]

    def edge_weight(self, u: int, v: int) -> Optional[float]:
        """Weight of edge (u, v), or None when absent.  O(log deg(u)) —
        rows are dst-sorted by construction (from_edges sorts by
        src * n + dst)."""
        lo, hi = int(self.indptr[u]), int(self.indptr[u + 1])
        i = lo + int(np.searchsorted(self.indices[lo:hi], v))
        if i < hi and int(self.indices[i]) == v:
            return float(self.weights[i])
        return None


@dataclasses.dataclass
class BlockedGraph:
    """Device-side block-ELL dense-tile layout (see module docstring)."""

    n_real: int          # number of real vertices
    block_size: int      # Vb (MXU-aligned, multiple of 128 on real TPU)
    num_blocks: int      # B_N
    max_nbr_blocks: int  # K
    fill: float          # 0.0 (plus-times) or +inf (min-plus)
    nbr_ids: jnp.ndarray   # [B_N, K] int32, padded entries point at block 0
    nbr_mask: jnp.ndarray  # [B_N, K] bool, True where the tile is real
    tiles: jnp.ndarray     # [B_N, K, Vb, Vb] float32
    vertex_mask: jnp.ndarray  # [B_N, Vb] bool, True for real vertices

    @property
    def n_padded(self) -> int:
        return self.num_blocks * self.block_size

    def tree_flatten(self):
        leaves = (self.nbr_ids, self.nbr_mask, self.tiles, self.vertex_mask)
        aux = (self.n_real, self.block_size, self.num_blocks,
               self.max_nbr_blocks, self.fill)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        n_real, block_size, num_blocks, max_nbr_blocks, fill = aux
        nbr_ids, nbr_mask, tiles, vertex_mask = leaves
        return cls(n_real, block_size, num_blocks, max_nbr_blocks, fill,
                   nbr_ids, nbr_mask, tiles, vertex_mask)


@dataclasses.dataclass
class BlockPairs:
    """Destination-sorted sparse block-pair view of a BlockedGraph.

    The block-ELL layout pads every source block to K neighbour slots, so
    a dense sweep stages zero-tiles as real bytes.  This view materializes
    ONLY the nonzero (src_block, dst_block) pairs, sorted by destination
    (NXgraph's destination-sorted sub-shards): consecutive pairs share a
    destination block, so a kernel sweeping the list in order revisits
    each output block while it is still VMEM-resident — one accumulation
    run and ONE flush per destination block (kernels.fused_superstep).

      src   [P] int32    source block of each pair
      dst   [P] int32    destination block, NON-DECREASING (dst-sorted)
      slot  [P] int32    the pair's ELL slot k (tiles[src, slot] is its tile)
      first [P] int32    1 at the first pair of each dst run (init point)
      last  [P] int32    1 at the last pair of each dst run (flush point)
      src_nnz [B_N] int32   real pairs per SOURCE block — staging block b
                            moves src_nnz[b] * Vb^2 * 4 real adjacency
                            bytes, the quantity `tile_pair_loads` accounts
      tiles [P, Vb, Vb] f32   contiguous dst-sorted copy of the pair tiles
      dense_op  [B_N*Vb, B_N*Vb] f32 or None — the full adjacency operator
                (row u, col v = weight of edge u->v), built only for
                plus-times views (fill == 0.0) dense enough to fit the
                byte cap.  A REFERENCE view for tests and contract
                checks: the engine pushes through the pair einsum /
                scatter (a [J, N] @ [N, N] matmul would let XLA pick a
                J-dependent contraction blocking, breaking the bit-for-
                bit job-axis sharding invariance dist.graph pins), and
                dist.graph drops it under a mesh.

    An edgeless graph keeps P >= 1 with one inert pad pair (src=dst=0,
    all-`fill` tile — an exact no-op in both semirings, src_nnz all 0).
    """

    num_pairs: int
    block_size: int
    num_blocks: int
    src: jnp.ndarray
    dst: jnp.ndarray
    slot: jnp.ndarray
    first: jnp.ndarray
    last: jnp.ndarray
    src_nnz: jnp.ndarray
    tiles: jnp.ndarray
    dense_op: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        leaves = (self.src, self.dst, self.slot, self.first, self.last,
                  self.src_nnz, self.tiles, self.dense_op)
        aux = (self.num_pairs, self.block_size, self.num_blocks)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*aux, *leaves)


#: build_block_pairs materializes `dense_op` only when the block graph is
#: at least this dense (P / B_N^2) AND the operator stays under the byte
#: cap — past either bound the pair list is the only materialization
DENSE_OP_MIN_DENSITY = 0.25
DENSE_OP_MAX_BYTES = 64 * 2**20


def build_block_pairs(g: BlockedGraph, *,
                      dense_min_density: float = DENSE_OP_MIN_DENSITY,
                      dense_max_bytes: int = DENSE_OP_MAX_BYTES
                      ) -> BlockPairs:
    """Destination-sorted real-pair view of `g` (see BlockPairs).

    Pure function of the CURRENT tiles: evolving views (repro.stream)
    rebuild it after in-place tile edits / compaction — the pair tiles
    are a copy, not an alias."""
    import jax
    # explicit device_get: pair building is host-side enumeration and may
    # run under the transfer sentinel (analysis.sentinels)
    ids, msk = map(np.asarray, jax.device_get((g.nbr_ids, g.nbr_mask)))
    bn, vb = g.num_blocks, g.block_size
    sb, slot = np.nonzero(msk)
    db = ids[sb, slot]
    src_nnz = np.bincount(sb, minlength=bn).astype(np.int32)
    if len(sb) == 0:
        # inert pad pair: an all-fill tile is an exact no-op (plus-times
        # adds 0.0, min-plus mins +inf), so P stays >= 1 for fixed shapes
        return BlockPairs(
            num_pairs=1, block_size=vb, num_blocks=bn,
            src=jnp.zeros(1, jnp.int32), dst=jnp.zeros(1, jnp.int32),
            slot=jnp.zeros(1, jnp.int32), first=jnp.ones(1, jnp.int32),
            last=jnp.ones(1, jnp.int32),
            src_nnz=jnp.asarray(src_nnz),
            tiles=jnp.full((1, vb, vb), g.fill, jnp.float32))
    order = np.lexsort((sb, db))          # dst-major, src ascending within
    sb, db, slot = sb[order], db[order], slot[order]
    first = np.ones(len(sb), np.int32)
    first[1:] = (db[1:] != db[:-1]).astype(np.int32)
    last = np.ones(len(sb), np.int32)
    last[:-1] = first[1:]
    tiles = g.tiles[jnp.asarray(sb), jnp.asarray(slot)]   # [P, Vb, Vb] copy
    dense_op = None
    density = len(sb) / float(bn * bn)
    if (g.fill == 0.0 and density >= dense_min_density
            and (bn * vb) ** 2 * 4 <= dense_max_bytes):
        op = jnp.zeros((bn, vb, bn, vb), jnp.float32)
        op = op.at[jnp.asarray(sb), :, jnp.asarray(db), :].set(tiles)
        dense_op = op.reshape(bn * vb, bn * vb)
    return BlockPairs(
        num_pairs=len(sb), block_size=vb, num_blocks=bn,
        src=jnp.asarray(sb, jnp.int32), dst=jnp.asarray(db, jnp.int32),
        slot=jnp.asarray(slot, jnp.int32),
        first=jnp.asarray(first), last=jnp.asarray(last),
        src_nnz=jnp.asarray(src_nnz),
        tiles=tiles, dense_op=dense_op)


@dataclasses.dataclass
class TileOverlay:
    """Bounded per-block delta-COO staged alongside the base tiles.

    Evolving graphs mutate while jobs run (repro.stream).  Most edge
    updates edit the dense base tile in place (the (src block, dst block)
    pair already owns a tile slot); an insert that creates a NEW block
    pair has nowhere to land in the block-ELL layout, so it goes into
    this overlay: for each source block, up to `capacity` explicit COO
    edges.  Staging block b stages its tile row AND its overlay row
    together (one `tile_loads` unit — the overlay rides along, it is not
    a second staging), and every push consumes both.  When a block's
    overlay row fills up, the owning view COMPACTS: the BlockedGraph is
    rebuilt from the updated CSR (bit-identical to a from-scratch build)
    and the overlay empties.

    Entries with mask 0 are inert by construction: plus-times adds an
    exact 0.0, min-plus mins an inf — so a capacity-0 overlay (the state
    of every never-updated view) leaves all pre-existing runs bitwise
    unchanged.

      src_u [B_N, C] int32   source vertex offset within the block
      dst   [B_N, C] int32   destination vertex, global padded index
      w     [B_N, C] float32 edge weight in the VIEW's weight space
                             (normalization already applied)
      mask  [B_N, C] float32 1.0 where the entry is a real edge
    """

    capacity: int
    src_u: jnp.ndarray
    dst: jnp.ndarray
    w: jnp.ndarray
    mask: jnp.ndarray

    def tree_flatten(self):
        return (self.src_u, self.dst, self.w, self.mask), (self.capacity,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(aux[0], *leaves)


def empty_overlay(num_blocks: int, capacity: int = 0) -> TileOverlay:
    """All-inert overlay; capacity 0 is the no-updates-yet default."""
    shape = (num_blocks, capacity)
    return TileOverlay(
        capacity=capacity,
        src_u=jnp.zeros(shape, dtype=jnp.int32),
        dst=jnp.zeros(shape, dtype=jnp.int32),
        w=jnp.zeros(shape, dtype=jnp.float32),
        mask=jnp.zeros(shape, dtype=jnp.float32))


import jax.tree_util  # noqa: E402

jax.tree_util.register_pytree_node(
    BlockedGraph, BlockedGraph.tree_flatten, BlockedGraph.tree_unflatten)
jax.tree_util.register_pytree_node(
    TileOverlay, TileOverlay.tree_flatten, TileOverlay.tree_unflatten)
jax.tree_util.register_pytree_node(
    BlockPairs, BlockPairs.tree_flatten, BlockPairs.tree_unflatten)


def build_blocked(csr: CSRGraph, block_size: int, *,
                  fill: float = 0.0,
                  normalize: Optional[str] = None) -> BlockedGraph:
    """Partition a CSR graph into dense [Vb, Vb] tiles, block-ELL layout.

    normalize:
      None          - raw edge weights
      "out_degree"  - weight / out_degree(src)   (PageRank-style stochastic)
      "unit"        - every present edge gets weight 1.0
      "zero"        - every present edge gets weight 0.0 (min-plus label prop)
    """
    n = csr.n
    vb = block_size
    bn = -(-n // vb)  # ceil

    src = np.repeat(np.arange(n, dtype=np.int64), csr.out_degree)
    dst = csr.indices.astype(np.int64)
    w = csr.weights.astype(np.float32).copy()
    if normalize == "out_degree":
        deg = np.maximum(csr.out_degree, 1).astype(np.float32)
        w = w / deg[src]
    elif normalize == "unit":
        w = np.ones_like(w)
    elif normalize == "zero":
        w = np.zeros_like(w)
    elif normalize is not None:
        raise ValueError(f"unknown normalize={normalize!r}")

    sb, db = src // vb, dst // vb
    su, dv = src % vb, dst % vb

    # enumerate distinct (src block, dst block) tile pairs
    pair_key = sb * bn + db
    order = np.argsort(pair_key, kind="stable")
    pair_key_s = pair_key[order]
    uniq_keys, first_idx = np.unique(pair_key_s, return_index=True)
    tile_sb = (uniq_keys // bn).astype(np.int32)
    tile_db = (uniq_keys % bn).astype(np.int32)

    # per-src-block neighbour count -> K
    counts = np.bincount(tile_sb, minlength=bn)
    k_max = max(int(counts.max(initial=0)), 1)

    nbr_ids = np.zeros((bn, k_max), dtype=np.int32)
    nbr_mask = np.zeros((bn, k_max), dtype=bool)
    tiles = np.full((bn, k_max, vb, vb), fill, dtype=np.float32)

    # slot index of each tile within its src block row
    slot_of_key = {}
    next_slot = np.zeros(bn, dtype=np.int64)
    for tkey, tsb, tdb in zip(uniq_keys, tile_sb, tile_db):
        s = next_slot[tsb]
        slot_of_key[int(tkey)] = int(s)
        nbr_ids[tsb, s] = tdb
        nbr_mask[tsb, s] = True
        next_slot[tsb] += 1

    slots = np.fromiter((slot_of_key[int(k)] for k in pair_key),
                        dtype=np.int64, count=len(pair_key))
    tiles[sb, slots, su, dv] = w

    vmask = np.zeros((bn, vb), dtype=bool)
    vmask.reshape(-1)[:n] = True

    return BlockedGraph(
        n_real=n, block_size=vb, num_blocks=bn, max_nbr_blocks=k_max,
        fill=float(fill),
        nbr_ids=jnp.asarray(nbr_ids), nbr_mask=jnp.asarray(nbr_mask),
        tiles=jnp.asarray(tiles), vertex_mask=jnp.asarray(vmask))
