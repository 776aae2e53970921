"""Fused CAJS superstep megakernel over sparse block pairs.

One Pallas program per (job-chunk, block-pair) fuses the paper's inner
loop — stage the staged-selection deltas and push them for EVERY
concurrent job — without HBM round-trips between the stages:

  grid (J/Jb, P)   p innermost over `BlockPairs` order: pairs are
                   DESTINATION-sorted, so consecutive p share the output
                   block.  Pallas keeps a block resident while its
                   index_map output is unchanged, so the accumulator for
                   a destination block lives in VMEM across its whole
                   run of pairs and is flushed to HBM exactly once.
                   The grid pipeline double-buffers the next pair's
                   adjacency tile fetch behind the current dot/min.
  block-major      the kernel sees job state BLOCK-major, [B, J, Vb]:
                   Mosaic tiles the last two dimensions of every block
                   (sublane x lane), so the per-pair block index must
                   lead and each block is a dense [Jb, Vb] stripe (Jb ==
                   J or a multiple of 8).  The public call keeps the
                   job-major [J, B, Vb] contract and transposes at the
                   boundary.
  scalar prefetch  (src, dst, first, last) pair metadata is prefetched
                   as scalars (PrefetchScalarGridSpec) and drives the
                   data-dependent index_maps.  The four [P] int32 arrays
                   live in SMEM (1 MiB on v5e), so a long pair list is
                   swept by a chain of calls over PAIR_CHUNK pairs each.
                   The outputs alias the state inputs: blocks a call
                   never visits keep their input value, and a dst run
                   split across two calls continues from the first
                   call's partial result (exact for both semirings).
  @pl.when(first)  initialize the accumulator from the consumed base
                   (plus-times) / reset the min candidate (min-plus).
  @pl.when(last)   min-plus flush: fold the candidate into values and
                   the improved vertices into the pending deltas.

Selection is encoded entirely in the operand: the wrapper masks
non-selected source rows to the semiring identity (0 / +inf), so their
contributions vanish EXACTLY and no validity flags enter the kernel —
padded selection slots can alias block 0 without re-pushing it.

plus-times accumulates on the MXU ([Jb, Vb] @ [Vb, Vb], full f32
precision); min-plus has no MXU analogue and min-folds on the VPU: for
each source vertex v, a lane-broadcast job column d[:, v] plus the
sublane-broadcast tile row t[v, :], over row chunks of 8 jobs so the
running candidate stays in vector registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: pairs per pallas_call: 4 x 32768 int32 prefetch scalars = 512 KiB of
#: the 1 MiB SMEM (the compiler refuses P=72034 in one call: 1.11M used)
PAIR_CHUNK = 32768

#: job rows per min-plus fold chunk: one f32 sublane tile
_ROWS = 8


def _plus_kernel(psrc, pdst, pfirst, plast,      # scalar-prefetch refs
                 d_ref, base_ref, t_ref,         # [1,Jb,Vb] x2, [1,Vb,Vb]
                 o_ref):                         # [1,Jb,Vb]
    p = pl.program_id(1)

    @pl.when(pfirst[p] == 1)
    def _init():
        o_ref[0] = base_ref[0]

    o_ref[0] += jnp.dot(d_ref[0], t_ref[0],
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)


def _min_kernel(psrc, pdst, pfirst, plast,
                d_ref, vbase_ref, dbase_ref, t_ref,
                vo_ref, do_ref,
                cand):                           # VMEM scratch [Jb, Vb]
    p = pl.program_id(1)

    @pl.when(pfirst[p] == 1)
    def _init():
        cand[...] = jnp.full(cand.shape, jnp.inf, jnp.float32)

    t = t_ref[0]
    jb, vb = cand.shape
    rows = _ROWS if jb % _ROWS == 0 else jb

    def body(r, _):
        lo = pl.multiple_of(r * rows, rows)
        d = d_ref[0, pl.ds(lo, rows), :]                          # [R, Vb]
        acc = cand[pl.ds(lo, rows), :]
        for v in range(vb):                      # static: Vb source rows
            acc = jnp.minimum(acc, d[:, v:v + 1] + t[v:v + 1, :])
        cand[pl.ds(lo, rows), :] = acc
        return 0

    jax.lax.fori_loop(0, jb // rows, body, 0)

    @pl.when(plast[p] == 1)
    def _flush():
        v_old = vbase_ref[0]
        v_new = jnp.minimum(v_old, cand[...])
        vo_ref[0] = v_new
        do_ref[0] = jnp.minimum(dbase_ref[0],
                                jnp.where(v_new < v_old, v_new, jnp.inf))


def fused_superstep_call(src, dst, first, last, d, base, tiles, *,
                         values=None, semiring: str = "plus_times",
                         job_block: int | None = None,
                         interpret: bool = False):
    """One fused push over destination-sorted pairs.

    src/dst/first/last [P] int32 (`BlockPairs` metadata, dst-sorted);
    d [J, B_N, Vb] consumed pending deltas with NON-selected source rows
    already masked to the semiring identity (0 / +inf), pre-scaled for
    plus-times; base [J, B_N, Vb] post-consume deltas; tiles [P, Vb, Vb].

    plus-times  -> delta_out
    min-plus    -> (values_out, delta_out)   (`values` required:
                   [J, B_N, Vb] current values)

    Blocks that appear as no pair's destination pass through unchanged
    (base / values).  Output width follows `base`: a 2D-mesh block shard
    (repro.dist.mesh2d) passes d at the GLOBAL source width [J, B_N, Vb]
    with base/values (and dst entries) at its LOCAL dst width
    [J, B_loc, Vb]; unsharded callers pass both at B_N.
    `job_block` must divide J and be J or a multiple of 8 to compile
    (interpret mode accepts any divisor).
    """
    return _fused_jit(src, dst, first, last, d, base, tiles, values,
                      semiring=semiring, job_block=job_block,
                      interpret=bool(interpret))


def _block_major(x):
    return jnp.swapaxes(x, 0, 1)


@functools.partial(jax.jit, static_argnames=("semiring", "job_block",
                                             "interpret"))
def _fused_jit(src, dst, first, last, d, base, tiles, values, *,
               semiring, job_block, interpret):
    # output width follows BASE, not d: a 2D-mesh shard passes the full
    # global-source-indexed operand d [J, B_N, Vb] (what src[pp] indexes)
    # with base/values/outputs at its LOCAL dst width [J, B_loc, Vb]
    # (what dst[pp] indexes) — identical shapes in the unsharded call
    j, _, vb = d.shape
    p = src.shape[0]
    jb = job_block or j
    assert j % jb == 0, f"J={j} not divisible by job_block={jb}"
    plus = semiring == "plus_times"
    if not plus:
        assert values is not None, "min-plus fused call needs `values`"

    def dmap(jt, pp, src, dst, first, last):
        return (src[pp], jt, 0)

    def omap(jt, pp, src, dst, first, last):
        return (dst[pp], jt, 0)

    state_spec = pl.BlockSpec((1, jb, vb), omap)
    d_spec = pl.BlockSpec((1, jb, vb), dmap)
    d_bm = _block_major(d)
    state = ((_block_major(base),) if plus
             else (_block_major(values), _block_major(base)))
    for lo in range(0, p, PAIR_CHUNK):
        hi = min(lo + PAIR_CHUNK, p)

        def tmap(jt, pp, src, dst, first, last, lo=lo):
            return (pp + lo, 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(j // jb, hi - lo),
            in_specs=[d_spec, *[state_spec] * len(state),
                      pl.BlockSpec((1, vb, vb), tmap)],
            out_specs=[state_spec] * len(state),
            scratch_shapes=([] if plus
                            else [pltpu.VMEM((jb, vb), jnp.float32)]))
        # a chunk opens and closes every dst run it holds; a run split
        # over two chunks resumes from the aliased partial state
        state = pl.pallas_call(
            _plus_kernel if plus else _min_kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype)
                       for s in state],
            input_output_aliases={5 + i: i for i in range(len(state))},
            interpret=interpret,
        )(src[lo:hi], dst[lo:hi], first[lo:hi].at[0].set(1),
          last[lo:hi].at[-1].set(1), d_bm, *state, tiles)
        state = tuple(state)
    if plus:
        return _block_major(state[0])
    return _block_major(state[0]), _block_major(state[1])
