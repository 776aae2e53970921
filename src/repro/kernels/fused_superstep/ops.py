"""Jit wrappers around the fused superstep megakernel.

`fused_push` mirrors the engine shared-mode push exactly (consume the
selected blocks' pending deltas, push for every job, fold values), with
the whole select→stage→push chain lowered into ONE Pallas
program over the view's destination-sorted `BlockPairs`.  The fold /
consume bookkeeping stays in jnp (bandwidth-bound on state vectors, not
adjacency); selection enters the kernel only as identity-masked operand
rows, so padded selection slots aliasing block 0 cannot re-push it.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.common import resolve_interpret
from repro.kernels.fused_superstep.kernel import fused_superstep_call


def _pick_job_block(j: int, vb: int, semiring: str) -> int:
    """Largest job chunk whose per-grid-cell footprint fits the budget.

    Mosaic tiles a block's last two dims, so the kernel's [1, Jb, Vb]
    state blocks need Jb == J or Jb a multiple of 8: the candidates are
    J itself and its multiple-of-8 divisors (a J with none — e.g. a
    prime — has only J).  Footprint per `block_vmem_bytes`; past the
    budget the smallest candidate wins."""
    cands = [jb for jb in range(j, 0, -1)
             if j % jb == 0 and (jb == j or jb % 8 == 0)]
    for jb in cands:
        if block_vmem_bytes(jb, vb, semiring) <= common.VMEM_BUDGET:
            return jb
    return cands[-1]


def block_vmem_bytes(jb: int, vb: int, semiring: str) -> int:
    """VMEM one grid cell holds at job chunk `jb`: every block is padded
    to (8, 128) f32 tiles and double-buffered by the grid pipeline."""
    lanes = -(-vb // 128) * 128
    tile = -(-vb // 8) * 8 * lanes * 4
    stripe = -(-jb // 8) * 8 * lanes * 4
    # plus-times: d, base in + deltas out; min-plus: d, values, deltas in
    # + values, deltas out, and one undoubled candidate scratch
    stripes = 2 * 3 if semiring == "plus_times" else 2 * 5 + 1
    return 2 * tile + stripes * stripe


def fused_push(values: jnp.ndarray, deltas: jnp.ndarray, pairs,
               sel_ids: jnp.ndarray, sel_mask: jnp.ndarray,
               push_scale: jnp.ndarray, *, semiring: str = "plus_times",
               interpret: bool | None = None):
    """Megakernel-backed CAJS push. values/deltas [J, B_N, Vb].

    `pairs` is the view's `graph.structure.BlockPairs`.  Returns updated
    (values, deltas).  ``interpret=None`` resolves through
    `kernels.common.resolve_interpret`."""
    j, bn, vb = values.shape
    interpret = resolve_interpret(interpret)
    jb = _pick_job_block(j, vb, semiring)
    selb = jnp.zeros((bn,), jnp.bool_).at[sel_ids].max(sel_mask > 0)
    selb = selb[None, :, None]
    if semiring == "plus_times":
        raw = jnp.where(selb, deltas, 0.0)
        deltas = fused_superstep_call(
            pairs.src, pairs.dst, pairs.first, pairs.last,
            raw * push_scale[:, None, None], deltas - raw, pairs.tiles,
            semiring=semiring, job_block=jb, interpret=interpret)
        return values + raw, deltas
    pend = jnp.where(selb, deltas, jnp.inf)
    base = jnp.where(selb, jnp.inf, deltas)
    return fused_superstep_call(
        pairs.src, pairs.dst, pairs.first, pairs.last, pend, base,
        pairs.tiles, values=values, semiring=semiring, job_block=jb,
        interpret=interpret)
