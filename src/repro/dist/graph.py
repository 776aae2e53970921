"""Job-axis sharding for concurrent graph runs — multi-device CAJS.

The paper's premise is J concurrent jobs sharing one graph.  On a multi-
device mesh the natural SPMD extension keeps the locality story intact:

  * adjacency tiles / neighbour ids are REPLICATED — every device stages a
    selected block once into its local memory and serves all jobs resident
    on that device (CAJS per device, NXgraph-style locality-first staging);
  * the stacked job state (values/deltas [J, B_N, Vb], push_scale [J]) is
    SHARDED over a "jobs" mesh axis — each device advances J/D jobs.

Because every per-job computation in the engine is a vmap over the job axis,
partitioning that axis changes the device assignment but not a single
arithmetic op per job: the sharded run converges to the SAME fixpoint,
bit-for-bit, as the single-device run (asserted by tests/test_dist_graph.py).

Jobs that do not divide the axis fall back to replication for the remainder-
free guarantee (documented, not silently wrong).

Composition with the device-resident scheduler (core.policy,
backend="device"): the compiled superstep takes each group's
values/deltas/push_scale and the replicated tiles as ARGUMENTS, so the
placement below flows straight into the jitted scan/while_loop — jax
re-specializes the cached compilation on the new shardings, the per-job DO
sampling and pushes partition along the job axis, and the only cross-device
traffic per superstep is the global-queue scatter-add and the scalar
convergence all-reduce.  With steps_per_sync=K even those stay on device
for K supersteps per host round-trip.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

JOB_AXIS = "jobs"


def make_job_mesh(n_devices: Optional[int] = None,
                  axis_name: str = JOB_AXIS) -> Mesh:
    """1-D mesh over the first n_devices devices (default: all)."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if not 1 <= n <= len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]).reshape(n), (axis_name,))


def job_sharding(mesh: Mesh, axis_name: Optional[str] = None,
                 ndim: int = 3) -> NamedSharding:
    """NamedSharding for a [J, ...] stacked job tensor."""
    axis = axis_name or mesh.axis_names[0]
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def _replicated(mesh: Mesh, x) -> jax.Array:
    return jax.device_put(x, NamedSharding(mesh, P(*([None] * x.ndim))))


def shard_job_state(mesh: Mesh, values, deltas, push_scale, graph,
                    axis_name: Optional[str] = None, view_key=None):
    """Place stacked job state on `mesh`: values/deltas/push_scale sharded
    over the job axis, the shared graph replicated (mutated in place — it is
    the shared view by design).  Used by GraphSession and shard_run alike;
    a session's padded [J_cap, ...] axis shards exactly like a fixed [J, ...]
    one because free slots are inert."""
    axis = axis_name or mesh.axis_names[0]
    n_shard = mesh.shape[axis]
    j = values.shape[0]
    if j % n_shard == 0:
        jobs3 = job_sharding(mesh, axis, ndim=3)
        jobs1 = job_sharding(mesh, axis, ndim=1)
    else:  # remainder jobs: replicate rather than pad (identical math)
        if n_shard > 1:
            from repro.dist.mesh2d import warn_layout_once
            warn_layout_once(view_key if view_key is not None else ("run",),
                             axis, n_shard, j, "jobs-replicated")
        jobs3 = NamedSharding(mesh, P(None, None, None))
        jobs1 = NamedSharding(mesh, P(None))
    graph.tiles = _replicated(mesh, graph.tiles)
    graph.nbr_ids = _replicated(mesh, graph.nbr_ids)
    graph.nbr_mask = _replicated(mesh, graph.nbr_mask)
    graph.vertex_mask = _replicated(mesh, graph.vertex_mask)
    return (jax.device_put(values, jobs3),
            jax.device_put(deltas, jobs3),
            jax.device_put(push_scale, jobs1))


def shard_session(mesh: Mesh, session, axis_name: Optional[str] = None,
                  axes=None, *, compress_halo: bool = False, bits: int = 8):
    """Place a (possibly heterogeneous) GraphSession on `mesh`: EVERY view
    group's job axis is sharded independently (each view keeps its own
    padded [J_view_cap, B_N, Vb] state) and every view's tiles are
    replicated, so each device stages a selected block once per view and
    serves all jobs resident on it.  Groups whose job axis does not divide
    the mesh fall back to replication (identical math), per group — a
    divisible plus-times group shards even when the min-plus group cannot.

    `axes=("jobs", "blocks")` (or any mesh with >= 2 named axes) selects
    the 2D placement instead: job state shards over BOTH axes and each
    block shard owns its `BlockPairs` slice + the destination rows it
    updates, exchanging only frontier deltas per superstep — see
    repro.dist.mesh2d (`compress_halo`/`bits` apply only there).

    The delta-COO overlay of an evolving view (repro.stream) is SHARED
    graph data exactly like the tiles, so it replicates with them: each
    device stages a block's overlay row alongside its tile for its local
    jobs.  Job state stays sharded across update batches — apply_updates
    touches values/deltas with .at scatters, which preserve placement."""
    import dataclasses as _dc
    if axes is not None or len(mesh.axis_names) >= 2:
        from repro.dist.mesh2d import shard_session_2d
        ax = tuple(axes) if axes is not None else tuple(mesh.axis_names[:2])
        return shard_session_2d(mesh, session, axes=ax,
                                compress_halo=compress_halo, bits=bits)
    from repro.dist.mesh2d import unshard_session as _unshard2d
    _unshard2d(session)   # leaving a 2D mesh for a 1D one
    for grp in session.view_groups():
        grp.values, grp.deltas, grp.push_scale = shard_job_state(
            mesh, grp.values, grp.deltas, grp.push_scale, grp.graph,
            axis_name, view_key=grp.key)
        if grp.overlay is not None:
            grp.overlay = _dc.replace(
                grp.overlay,
                src_u=_replicated(mesh, grp.overlay.src_u),
                dst=_replicated(mesh, grp.overlay.dst),
                w=_replicated(mesh, grp.overlay.w),
                mask=_replicated(mesh, grp.overlay.mask))
        # the destination-sorted block-pair view is shared adjacency data
        # exactly like the tiles: build it now (from the just-replicated
        # tiles) and replicate every leaf, so the fused megakernel sweep
        # stages each pair once per device for its local jobs.
        # dense_op is DROPPED under a mesh: the engine never pushes
        # through it (a [J, N] @ [N, N] matmul would let XLA pick a
        # J-dependent contraction blocking, breaking the bit-for-bit
        # sharding invariance this module guarantees — the pair einsum /
        # scatter reduces per (job, pair) independently instead), so
        # replicating an [N, N] dense operator would waste HBM.
        bp = session._pair_data(grp)
        grp.pairs = _dc.replace(
            bp,
            src=_replicated(mesh, bp.src), dst=_replicated(mesh, bp.dst),
            slot=_replicated(mesh, bp.slot),
            first=_replicated(mesh, bp.first),
            last=_replicated(mesh, bp.last),
            src_nnz=_replicated(mesh, bp.src_nnz),
            tiles=_replicated(mesh, bp.tiles),
            dense_op=None)
    return session


def unshard_session(session):
    """Gather a 2D-placed session back to single-device placement (no-op
    for 1D job-axis placements, which never commit state off-mesh)."""
    from repro.dist.mesh2d import unshard_session as _unshard2d
    return _unshard2d(session)


def shard_run(run, mesh: Mesh, axis_name: Optional[str] = None):
    """Place a ConcurrentRun on `mesh`: job state sharded over the job axis,
    graph replicated.  Returns a new ConcurrentRun."""
    values, deltas, push_scale = shard_job_state(
        mesh, run.values, run.deltas, run.push_scale, run.graph, axis_name)
    return dataclasses.replace(
        run, values=values, deltas=deltas, push_scale=push_scale)
