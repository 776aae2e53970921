"""jnp reference oracle for the fused superstep megakernel.

Same contract as `kernel.fused_superstep_call`, written as plain gather /
scatter reductions — the parity target for the kernel tests.
"""

from __future__ import annotations

import jax.numpy as jnp


def fused_superstep_ref(src, dst, first, last, d, base, tiles, *,
                        values=None, semiring: str = "plus_times"):
    del first, last
    j, bn, vb = d.shape
    if semiring == "plus_times":
        contrib = jnp.einsum("jpv,pvw->jpw", d[:, src, :], tiles)
        return base.at[:, dst, :].add(contrib, mode="drop")
    assert values is not None
    cand_p = jnp.min(d[:, src, :, None] + tiles[None], axis=2)  # [J, P, Vb]
    cand = jnp.full((j, bn, vb), jnp.inf).at[:, dst, :].min(
        cand_p, mode="drop")
    v_new = jnp.minimum(values, cand)
    d_new = jnp.minimum(base, jnp.where(v_new < values, v_new, jnp.inf))
    return v_new, d_new
