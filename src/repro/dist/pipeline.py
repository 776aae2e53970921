"""Microbatched pipeline parallelism (GPipe schedule) over a mesh axis.

Each device on the pipeline axis owns one stage's parameters (leading stage
axis of the params tree, sharded over the axis).  The batch is split into
n_micro microbatches; each scan tick every device runs its stage on its
current activation and ppermutes the result to the next stage — the rotating
systolic schedule.  After n_micro + S - 1 ticks the last stage has produced
all microbatch outputs; the loss is computed on the reassembled batch so the
pipelined loss (and, through AD, its grads) matches the unpipelined
sequential reference exactly.

Stages must be shape-homogeneous (activation in == activation out), which is
exactly the transformer-block case.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_pipelined_loss(mesh: Mesh, stage_fn: Callable, loss_fn: Callable,
                        axis_name: str = "pod", n_micro: int = 1):
    """Build pipelined(params, x, y) -> scalar loss.

    params: tree whose leaves carry a leading stage axis of size S =
    mesh.shape[axis_name].  stage_fn(stage_params, h) -> h' applies ONE
    stage (no stage axis).  loss_fn(out, y) -> scalar on the full batch.
    """
    n_stages = mesh.shape[axis_name]

    def _body(params, xs, y):
        p = jax.tree.map(lambda a: a[0], params)       # this device's stage
        idx = jax.lax.axis_index(axis_name)
        n_ticks = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        out_sd = jax.eval_shape(stage_fn, p, xs[0])
        if out_sd.shape != xs.shape[1:] :
            raise ValueError("pipeline stages must be shape-homogeneous: "
                             f"{xs.shape[1:]} -> {out_sd.shape}")

        def tick(carry, t):
            state, outs = carry
            # stage 0 injects microbatch t (clipped duplicates past the end
            # never reach a valid output slot before the loop ends)
            inp = jnp.where(idx == 0,
                            xs[jnp.clip(t, 0, n_micro - 1)], state)
            out = stage_fn(p, inp)
            w = t - (n_stages - 1)       # microbatch leaving the last stage
            cw = jnp.clip(w, 0, n_micro - 1)
            write = (idx == n_stages - 1) & (w >= 0)
            outs = outs.at[cw].set(jnp.where(write, out, outs[cw]))
            state = jax.lax.ppermute(out, axis_name, perm)
            return (state, outs), None

        outs0 = jnp.zeros((n_micro,) + out_sd.shape, out_sd.dtype)
        (_, outs), _ = jax.lax.scan(
            tick, (jnp.zeros_like(xs[0]), outs0),
            jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast them to all
        # devices so the (replicated) loss is computed identically everywhere
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis_name)
        full = outs.reshape((outs.shape[0] * outs.shape[1],) + outs.shape[2:])
        return loss_fn(full, y)

    sharded = jax.shard_map(_body, mesh=mesh,
                            in_specs=(P(axis_name), P(), P()),
                            out_specs=P(),
                            check_vma=False)

    def pipelined(params, x, y):
        batch = x.shape[0]
        if batch % n_micro:
            raise ValueError(f"batch {batch} not divisible by "
                             f"n_micro={n_micro}")
        mb = batch // n_micro
        xs = x.reshape((n_micro, mb) + x.shape[1:])
        return sharded(params, xs, y)

    return pipelined
