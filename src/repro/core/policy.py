"""Pluggable schedule policies over a GraphSession.

One driver pair replaces the four historical near-duplicate engine loops.
A policy decides, per superstep, WHICH blocks are staged and WHO processes
them; the driver owns everything else (convergence test, metrics, the push
dispatch).  All policies reach the same per-job fixpoint — they differ
only in schedule and therefore in tile_loads / supersteps:

  TwoLevel    - the paper: per-job DO queues -> global queue -> one staging
                of each selected block serves ALL jobs (CAJS + MPDS).
  Independent - redundancy baseline: each job selects and stages its own
                queue (paper Fig. 3 "current mode").
  AllBlocks   - non-prioritized baseline: every block, every superstep.
  Fused       - alias for TwoLevel(backend="device", steps_per_sync=inf):
                the entire loop in one on-device while_loop.

Every policy runs on either BACKEND:

  backend="host"   - the faithful Job Controller: scheduling on host
                     (numpy + exact CBP), push on device; one scheduling
                     sync per superstep.
  backend="device" - both scheduling levels execute inside ONE jitted
                     superstep (device do_select sampling via jax.random
                     with the seed threaded through fold_in(step), global
                     synthesis as a weighted scatter-add with reserved
                     head slots), fused with the push into a single
                     dispatch.  `steps_per_sync=K` lax.scan's K supersteps
                     per host round-trip (convergence is still detected
                     exactly: a scanned step no-ops once all jobs
                     converge); `steps_per_sync=math.inf` turns the scan
                     into a lax.while_loop that only returns at the
                     fixpoint.  Compiled steps are cached on the session
                     (`session._device_step_fn`), keyed on view keys /
                     capacities / q / alpha / steps_per_sync, so repeated
                     run() calls and resubmissions never re-trace.

`RunMetrics.host_syncs` counts scheduling round-trips (host backend: one
per superstep including the final all-converged poll; device backend: one
per scan chunk / while_loop return) — the quantity `steps_per_sync`
amortizes, swept by `benchmarks/run.py fig_sync`.

Sessions are HETEROGENEOUS (repro.core.session): jobs live in per-graph-
view groups, but block ids are view-agnostic (every view is block-aligned
over the same CSR), so scheduling stays a single two-level decision over
all jobs' DO queues.  A shared policy stages each selected block ONCE per
superstep and dispatches it through every view's push (the plus-times and
the min-plus semiring in the same superstep) — `tile_loads` counts that
staging once, which is what makes the cross-family CAJS saving measurable.

Each policy composes with `mesh=` job-axis placement (repro.dist.graph):
partitioning the vmapped job axes never changes per-job arithmetic, so the
sharded run converges to the same fixpoint.

Metric layout: `RunMetrics.iterations_per_job` concatenates view groups in
creation order (`GraphSession.job_index(handle)` maps a handle to its row;
== handle.slot for single-view sessions).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import List, Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import priority as prio
from repro.core.do_select import do_select_device
from repro.core.global_q import accumulate_priority, synthesize_topq
from repro.core.push import compute_pairs, indep_push_fn, shared_push_fn
from repro.obs.telemetry import (HostSeriesBuilder, TelemetrySeries,
                                 device_buffers, device_write,
                                 series_from_device)

HOST, DEVICE = "host", "device"


@dataclasses.dataclass
class RunMetrics:
    supersteps: int = 0
    tile_loads: int = 0            # adjacency-block stagings (HBM->VMEM)
    # real adjacency bytes: nonzero (src, dst) block pairs moved, summed
    # over pushed view groups (tile_pair_loads * Vb^2 * 4 bytes) — the
    # sparse BlockPairs refinement of tile_loads, which counts a staged
    # block once across views regardless of how many of its K ELL slots
    # are padding
    tile_pair_loads: int = 0
    job_block_pushes: int = 0      # (job, block) processing events
    host_syncs: int = 0            # scheduling host<->device round-trips
    # cross-shard frontier payload of a 2D (jobs x blocks) mesh run
    # (repro.dist.mesh2d): exchanged delta rows x Vb x itemsize, summed
    # over supersteps — proportional to frontier deltas, NEVER to whole
    # tiles; 0.0 off-mesh and on 1D job meshes (nothing block-crosses)
    halo_bytes: float = 0.0
    iterations_per_job: Optional[np.ndarray] = None
    converged: bool = False
    wall_time_s: float = 0.0       # driver wall time of this run()
    # evolving-graph counters (repro.stream), drained from the session's
    # apply_updates() calls since the previous run()
    updates_applied: int = 0       # edge insert/delete ops absorbed
    dirty_blocks: int = 0          # blocks marked update-affected
    reseed_fraction: float = 0.0   # re-seeded share of active job state
    # per-superstep series (repro.obs), only when the session was built
    # with telemetry=...; None otherwise
    telemetry: Optional[TelemetrySeries] = None

    def to_dict(self, include_telemetry: bool = False) -> dict:
        """Scalar record of this run — the ONE serialization used by the
        benchmark harness's JSON rows and the trace exporter's run spans
        (no ad-hoc string parsing in either)."""
        d = {"supersteps": int(self.supersteps),
             "tile_loads": int(self.tile_loads),
             "tile_pair_loads": int(self.tile_pair_loads),
             "job_block_pushes": int(self.job_block_pushes),
             "host_syncs": int(self.host_syncs),
             "halo_bytes": float(self.halo_bytes),
             "converged": bool(self.converged),
             "wall_time_s": round(float(self.wall_time_s), 6),
             "updates_applied": int(self.updates_applied),
             "dirty_blocks": int(self.dirty_blocks),
             "reseed_fraction": round(float(self.reseed_fraction), 6)}
        if include_telemetry and self.telemetry is not None:
            d["telemetry"] = self.telemetry.to_dict()
        return d


@dataclasses.dataclass
class Selection:
    """One superstep's staging decision.

    shared=True: `sel`/`msk` are [q] — ONE staging of each selected block
    serves every job in every view group (CAJS; tile_loads counted once).
    shared=False: `sel`/`msk` are per-group lists of [J_g, q] — each job
    stages its own queue (the redundancy baseline).

    Host policies fill it with numpy values; device policies return the
    same container holding tracers (consumed inside the jitted superstep).

    DTYPE CONTRACT for `tile_loads` / `job_block_pushes`: host `select`
    returns python `int`s; `device_select` returns int32 scalars (per-step
    values are tiny — the drivers coerce exactly once into their own
    accumulators, float32 on device so multi-million-superstep sums never
    wrap, int on host).  Pinned by tests/test_obs.py so telemetry series
    never silently mix dtypes.
    """

    sel: Union[np.ndarray, List[np.ndarray]]
    msk: Union[np.ndarray, List[np.ndarray]]
    shared: bool
    tile_loads: int
    job_block_pushes: int


class SchedulePolicy:
    """Base policy: subclasses implement `select` (host) / `device_select`.

    Both receive per-view-group lists (creation order): node_un[g] and
    p_mean[g] are [J_g, B_N], active[g] is [J_g] bool."""

    name = "abstract"
    needs_pairs = True  # driver computes <Node_un, P_mean> before select()

    def __init__(self, *, backend: str = HOST,
                 steps_per_sync: Union[int, float] = 1):
        if backend not in (HOST, DEVICE):
            raise ValueError(f"backend must be 'host' or 'device': {backend}")
        if backend == HOST:
            if steps_per_sync != 1:
                raise ValueError(
                    "host scheduling decides every superstep — "
                    "steps_per_sync requires backend='device'")
        elif steps_per_sync != math.inf and (
                steps_per_sync != int(steps_per_sync) or steps_per_sync < 1):
            raise ValueError(
                f"steps_per_sync must be a positive int or math.inf: "
                f"{steps_per_sync}")
        self.backend = backend
        self.steps_per_sync = steps_per_sync

    # -- selection hooks -----------------------------------------------------

    def select(self, sess, node_un: Optional[Sequence[np.ndarray]],
               p_mean: Optional[Sequence[np.ndarray]],
               active: Sequence[np.ndarray]) -> Optional[Selection]:
        """Host staging decision, or None when nothing is schedulable
        (the driver then declares convergence)."""
        raise NotImplementedError

    def device_select(self, node_uns, p_means, actives, key, *, q: int,
                      alpha: float, samples: int,
                      num_blocks: int) -> Selection:
        """Traced staging decision inside the jitted superstep.  `key` is
        this superstep's sampling key (already fold_in(step)-derived)."""
        raise NotImplementedError

    # -- driving -------------------------------------------------------------

    def run(self, sess, max_supersteps: int = 100000) -> RunMetrics:
        t0 = time.perf_counter()
        if self.backend == DEVICE:
            m = _run_device(self, sess, max_supersteps)
        else:
            m = _run_host(self, sess, max_supersteps)
        m.wall_time_s = time.perf_counter() - t0
        return m


def _profiler_span(sess, name: str):
    """jax.profiler annotation for one scheduling dispatch, opt-in via
    TelemetryConfig(jax_profiler=True); a no-op context otherwise."""
    cfg = getattr(sess, "telemetry", None)
    if cfg is not None and cfg.jax_profiler:
        return jax.profiler.TraceAnnotation(name)
    return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# host driver: counts fall out of the pairs dispatch; select on host
# ---------------------------------------------------------------------------


def _selection_occupancy(selection: Selection) -> int:
    """Staged-selection occupancy for telemetry: shared policies report the
    global-queue length (<= q), independent the total queue entries."""
    if selection.shared:
        return int(np.sum(np.asarray(selection.msk) > 0))
    return sum(int(np.sum(np.asarray(msk) > 0)) for msk in selection.msk)


def _run_host(policy: SchedulePolicy, sess,
              max_supersteps: int) -> RunMetrics:
    """Host driver: pairs -> select -> push, one scheduling sync per
    superstep.  The convergence counts are derived from the pairs
    (counts == node_un.sum(-1)), so policies that need pairs cost ONE
    device dispatch per group per superstep; AllBlocks keeps the cheaper
    counts-only reduction (needs_pairs=False fast path).

    Telemetry (repro.obs): with the session built telemetry=..., each
    superstep appends one row to a HostSeriesBuilder.  The max-residual
    column rides the SAME pairs/counts dispatch (with_resid variant), so
    telemetry never adds a host sync."""
    groups = sess.view_groups()
    offs = np.cumsum([0] + [g.capacity for g in groups])
    # on a 2D (jobs x blocks) mesh the push consumes the dst-partitioned
    # PairShards view instead (same global src_nnz, so the tile_pair_loads
    # accounting below is placement-agnostic)
    mesh2d = getattr(sess, "_mesh2d", None)
    if mesh2d is not None:
        grp_pairs = [sess._pair_shards(g) for g in groups]
    else:
        grp_pairs = [sess._pair_data(g) for g in groups]
    # host mirror of the per-source-block real-pair counts (explicit
    # device_get: the driver may run under the transfer sentinel)
    nnz_host = [np.asarray(x) for x in
                jax.device_get([p.src_nnz for p in grp_pairs])]
    m = RunMetrics(
        iterations_per_job=np.zeros(int(offs[-1]), dtype=np.int64))
    telemetry = getattr(sess, "telemetry", None) is not None
    if policy.needs_pairs:
        pairs_fns = [sess._pairs_fn(g, with_resid=telemetry)
                     for g in groups]
    else:
        counts_fns = [sess._counts_fn(g, with_resid=telemetry)
                      for g in groups]
    series = (HostSeriesBuilder([g.key for g in groups]) if telemetry
              else None)
    resids = [0.0] * len(groups)
    trace = getattr(sess, "trace", None)
    trace = trace if trace is not None and trace.enabled else None
    # a group observed fully converged stays converged for the rest of this
    # run (this driver never pushes an inactive group and no job can arrive
    # mid-run), so its per-superstep dispatch can be skipped outright; the
    # stand-in zeros are built on first skip only
    done = [None] * len(groups)
    bn = sess.scheduler.num_blocks
    # dirty-block priority injection (repro.stream): update-affected blocks
    # enter every job's DO queue boosted on the FIRST superstep after
    # apply_updates — only where the job actually has pending work there
    boost = sess._consume_dirty_boost()

    def _mark_done(gi):
        g = groups[gi]
        done[gi] = (np.zeros(g.capacity, dtype=bool),
                    np.zeros((g.capacity, bn), np.float32)
                    if policy.needs_pairs else None)

    for _ in range(max_supersteps):
        t_step = trace.now_us() if trace else 0.0
        dirty_n = int((boost > 0).sum()) if boost is not None else 0
        actives = []
        node_un = p_mean = None
        with _profiler_span(sess, "superstep.schedule"):
            if policy.needs_pairs:
                node_un, p_mean = [], []
                for gi, g in enumerate(groups):
                    if done[gi] is not None:
                        actives.append(done[gi][0])
                        node_un.append(done[gi][1])
                        p_mean.append(done[gi][1])
                        resids[gi] = 0.0
                        continue
                    out = pairs_fns[gi](g.values, g.deltas)
                    # the ONE intentional sync per group per superstep —
                    # explicit device_get keeps transfer_guard("disallow")
                    # clean (implicit float()/np coercions would trip it)
                    if telemetry:
                        nu, pm, rs = jax.device_get(out)
                        resids[gi] = float(rs)
                    else:
                        nu, pm = jax.device_get(out)
                    if boost is not None:
                        pm = pm + boost[None, :] * (nu > 0)
                    node_un.append(nu)
                    p_mean.append(pm)
                    actives.append(prio.counts_from_pairs(nu) > 0)
                    if not actives[gi].any():
                        _mark_done(gi)
            else:
                node_un = []
                for gi, g in enumerate(groups):
                    if done[gi] is not None:
                        actives.append(done[gi][0])
                        node_un.append(np.zeros(g.capacity,
                                                dtype=np.int32))
                        resids[gi] = 0.0
                        continue
                    out = counts_fns[gi](g.values, g.deltas)
                    if telemetry:
                        counts, rs = jax.device_get(out)
                        resids[gi] = float(rs)
                    else:
                        counts = jax.device_get(out)
                    node_un.append(counts)
                    actives.append(counts > 0)
                    if not actives[gi].any():
                        _mark_done(gi)
        for gi in range(len(groups)):
            m.iterations_per_job[offs[gi]:offs[gi + 1]][actives[gi]] += 1
        m.host_syncs += 1
        if not any(a.any() for a in actives):
            m.converged = True
            break
        boost = None
        selection = policy.select(
            sess, node_un if policy.needs_pairs else None, p_mean, actives)
        if selection is None:
            m.converged = True
            break
        # a fully-converged group is never pushed (matches the solo
        # session, which stops outright; for plus-times this also keeps
        # sub-tolerance residual mass where convergence left it)
        pair_step = 0
        with _profiler_span(sess, "superstep.push"):
            if selection.shared:
                sel = jnp.asarray(selection.sel)
                msk = jnp.asarray(selection.msk)
                sel_np = np.asarray(selection.sel)
                on_np = np.asarray(selection.msk) > 0
                for gi, g in enumerate(groups):
                    if not actives[gi].any():
                        continue
                    pair_step += int(nnz_host[gi][sel_np][on_np].sum())
                    g.values, g.deltas = sess._push_shared_fn(g)(
                        g.values, g.deltas, g.graph.tiles, g.graph.nbr_ids,
                        sel, msk, g.push_scale, g.overlay, grp_pairs[gi])
            else:
                for gi, g in enumerate(groups):
                    if not actives[gi].any():
                        continue
                    sel_np = np.asarray(selection.sel[gi])
                    on_np = np.asarray(selection.msk[gi]) > 0
                    pair_step += int((nnz_host[gi][sel_np] * on_np).sum())
                    args = (g.values, g.deltas, g.graph.tiles,
                            g.graph.nbr_ids,
                            jnp.asarray(selection.sel[gi]),
                            jnp.asarray(selection.msk[gi]), g.push_scale,
                            g.overlay)
                    if mesh2d is not None:   # 2D push needs the pair view
                        args = args + (grp_pairs[gi],)
                    g.values, g.deltas = sess._push_indep_fn(g)(*args)
        m.tile_pair_loads += pair_step
        halo_step = 0.0
        if mesh2d is not None:
            from repro.dist.mesh2d import host_halo_bytes
            halo_step = host_halo_bytes(mesh2d, groups, selection, actives)
            m.halo_bytes += halo_step
        if series is not None:
            # everything but pair_step/halo_step is a pre-push read; the
            # row is appended post-push only so those two can join it
            series.append(
                active_jobs=sum(int(a.sum()) for a in actives),
                tile_loads=int(selection.tile_loads),
                job_block_pushes=int(selection.job_block_pushes),
                gq_occupancy=_selection_occupancy(selection),
                dirty_blocks=dirty_n,
                unconverged=[int(np.sum(nu)) for nu in node_un],
                max_residual=resids,
                tile_pair_loads=pair_step, halo_bytes=halo_step)
        m.supersteps += 1
        # dtype contract: host selections carry python ints (coerced once)
        m.tile_loads += int(selection.tile_loads)
        m.job_block_pushes += int(selection.job_block_pushes)
        if trace:
            trace.complete("superstep", t_step, trace.now_us() - t_step,
                           cat="superstep", tid=2, step=m.supersteps - 1,
                           tile_loads=int(selection.tile_loads))
    if series is not None:
        m.telemetry = series.build()
    return m


# ---------------------------------------------------------------------------
# device driver: ONE jitted superstep, K supersteps per host round-trip
# ---------------------------------------------------------------------------


def build_device_step(policy: SchedulePolicy, sess):
    """Compile the session's superstep for `policy` into one jitted step
    function.  Returned callable:

        step_fn(state, scales, tiles, nbrs, overlays, pairs, max_steps,
                key) -> (state, unconverged_total)

    where state = (it, values_tuple, deltas_tuple, loads, pushes,
    pair_loads, iters_tuple, boost, telemetry_buffers) and `pairs` is the
    per-group `BlockPairs` tuple (the fused megakernel's adjacency view;
    `pair_loads` accumulates the real block pairs moved by pushed
    groups).  Finite steps_per_sync runs a lax.scan of that
    many gated supersteps (a step no-ops — and counts nothing — once all
    jobs converge or the budget is spent); steps_per_sync=inf runs a
    lax.while_loop to the fixpoint.  Graph tiles / neighbour ids / push
    scales — and each view's delta-COO overlay, so live update batches
    (repro.stream) never retrace — are ARGUMENTS, not closure constants:
    one compilation serves every run() call, resubmission, update batch,
    and mesh placement (jax re-specializes on sharding, not on values).
    `boost` is the dirty-block priority injection: [B_N] added to every
    group's P_mean (where pending) on the first superstep after
    apply_updates, then zeroed in the carry.

    `telemetry_buffers` (repro.obs) is () when the session has no
    telemetry — the series is COMPILED OUT, the program is bit-identical
    to the pre-observability superstep — and otherwise a tuple of
    preallocated [capacity] arrays written at min(it, capacity-1) each
    superstep, so a steps_per_sync=inf run returns the full per-superstep
    series at its single host sync.  The session's jit-cache key carries
    the capacity (0 when off), so toggling telemetry never invalidates or
    re-traces the other variant.  Cache via session._device_step_fn."""
    groups = sess.view_groups()
    n_groups = len(groups)
    algs = [g.alg for g in groups]
    q = int(sess.q)
    alpha = float(sess.alpha)
    samples = int(sess.samples)
    bn = int(sess.scheduler.num_blocks)
    k_sync = policy.steps_per_sync
    needs_pairs = policy.needs_pairs
    tel_cfg = getattr(sess, "telemetry", None)
    tel_cap = int(tel_cfg.capacity) if tel_cfg is not None else 0

    shared_push = [shared_push_fn(g.semiring, g.push_one, sess.use_pallas)
                   for g in groups]
    indep_push = [indep_push_fn(g.push_one) for g in groups]

    def unconverged_total(vs, ds):
        tot = jnp.int32(0)
        for gi in range(n_groups):
            tot = tot + jnp.sum(
                algs[gi].unconverged(vs[gi], ds[gi]).astype(jnp.int32))
        return tot

    def superstep(carry, scales, tiles, nbrs, ovs, prs, key):
        it, vs, ds, loads, pushes, pair_loads, iters, boost, tel = carry
        node_uns, p_means, actives = [], [], []
        for gi in range(n_groups):
            if needs_pairs:
                nu, pm = compute_pairs(algs[gi], vs[gi], ds[gi])
                pm = pm + boost[None, :] * (nu > 0)
            else:   # Node_un alone suffices (AllBlocks): cheaper reduce
                un = algs[gi].unconverged(vs[gi], ds[gi])
                nu = jnp.sum(un, axis=-1).astype(jnp.float32)
                pm = None
            node_uns.append(nu)
            p_means.append(pm)
            actives.append(prio.counts_from_pairs(nu) > 0)
        selection = policy.device_select(
            node_uns, p_means, actives, jax.random.fold_in(key, it),
            q=q, alpha=alpha, samples=samples, num_blocks=bn)
        new_vs, new_ds, new_iters = [], [], []
        pair_step = jnp.float32(0)
        for gi in range(n_groups):
            if selection.shared:
                v2, d2 = shared_push[gi](
                    vs[gi], ds[gi], tiles[gi], nbrs[gi],
                    selection.sel, selection.msk, scales[gi], ovs[gi],
                    prs[gi])
                pair_cnt = jnp.sum(prs[gi].src_nnz[selection.sel]
                                   * (selection.msk > 0))
            else:
                v2, d2 = indep_push[gi](
                    vs[gi], ds[gi], tiles[gi], nbrs[gi],
                    selection.sel[gi], selection.msk[gi], scales[gi],
                    ovs[gi])
                pair_cnt = jnp.sum(prs[gi].src_nnz[selection.sel[gi]]
                                   * (selection.msk[gi] > 0))
            # a fully-converged group is never pushed, exactly as in the
            # host driver: freezing it keeps sub-tolerance plus-times
            # residual mass where convergence left it (min-plus pushes
            # are exact no-ops either way)
            keep = jnp.any(actives[gi])
            new_vs.append(jnp.where(keep, v2, vs[gi]))
            new_ds.append(jnp.where(keep, d2, ds[gi]))
            new_iters.append(iters[gi] + actives[gi].astype(jnp.int32))
            pair_step = pair_step + (keep.astype(jnp.float32)
                                     * pair_cnt.astype(jnp.float32))
        if tel_cap:
            # the per-superstep series rides the carry: int32 rows written
            # at min(it, cap-1); pure reads of the pre-push state plus the
            # push loop's pair_step, so the push math — and the fixpoint —
            # is bitwise telemetry-off
            idx = jnp.minimum(it, tel_cap - 1)
            if selection.shared:
                occ = jnp.sum(selection.msk > 0).astype(jnp.int32)
            else:
                occ = sum(jnp.sum(msk > 0).astype(jnp.int32)
                          for msk in selection.msk)
            tel = device_write(
                tel, idx,
                sum(jnp.sum(a.astype(jnp.int32)) for a in actives),
                selection.tile_loads, selection.job_block_pushes, occ,
                jnp.sum(boost > 0).astype(jnp.int32),
                jnp.stack([jnp.sum(nu).astype(jnp.int32)
                           for nu in node_uns]),
                jnp.stack([jnp.max(algs[gi].vertex_priority(vs[gi],
                                                            ds[gi]))
                           for gi in range(n_groups)]),
                tile_pair_loads=pair_step.astype(jnp.int32))
        # dtype contract: device selections carry int32 scalars; the carry
        # accumulates in float32 (int32 would wrap on billion-push runs,
        # float32 only rounds past 2^24)
        return (it + 1, tuple(new_vs), tuple(new_ds),
                loads + selection.tile_loads.astype(jnp.float32),
                pushes + selection.job_block_pushes.astype(jnp.float32),
                pair_loads + pair_step,
                tuple(new_iters),
                jnp.zeros_like(boost),   # injection consumed: one superstep
                tel)

    def step_fn(state, scales, tiles, nbrs, ovs, prs, max_steps, key):
        def body(c):
            return superstep(c, scales, tiles, nbrs, ovs, prs, key)

        def live(c):
            return (unconverged_total(c[1], c[2]) > 0) & (c[0] < max_steps)

        if k_sync == math.inf:
            state = jax.lax.while_loop(live, body, state)
        else:
            def gated(c, _):
                return jax.lax.cond(live(c), body, lambda x: x, c), None
            state, _ = jax.lax.scan(gated, state, None, length=int(k_sync))
        return state, unconverged_total(state[1], state[2])

    return jax.jit(step_fn)


def device_step_args(sess, budget: int, boost=None) -> tuple:
    """The arguments of the session's compiled device superstep
    (`build_device_step`'s step_fn) for a run of at most `budget` (int32)
    supersteps: (state, scales, tiles, nbrs, overlays, pairs, max_steps,
    key).  Also what `step_fn.lower(*args)` takes to inspect the
    compiled program."""
    groups = sess.view_groups()
    bn = sess.scheduler.num_blocks
    tel_cfg = getattr(sess, "telemetry", None)
    tel_cap = int(tel_cfg.capacity) if tel_cfg is not None else 0
    state = (jnp.int32(0),
             tuple(g.values for g in groups),
             tuple(g.deltas for g in groups),
             jnp.float32(0), jnp.float32(0), jnp.float32(0),
             tuple(jnp.zeros(g.capacity, jnp.int32) for g in groups),
             jnp.zeros(bn, jnp.float32) if boost is None
             else jnp.asarray(boost, jnp.float32),
             device_buffers(tel_cap, len(groups)) if tel_cap else ())
    key = jax.random.fold_in(jax.random.PRNGKey(sess.seed),
                             sess.scheduler._step)
    return (state,
            tuple(g.push_scale for g in groups),
            tuple(g.graph.tiles for g in groups),
            tuple(g.graph.nbr_ids for g in groups),
            tuple(g.overlay for g in groups),
            tuple(sess._pair_data(g) for g in groups),
            jnp.int32(budget), key)


def _run_device(policy: SchedulePolicy, sess,
                max_supersteps: int) -> RunMetrics:
    """Device driver: call the cached jitted step, sync once per chunk.

    The sampling stream mirrors the host scheduler RNG's semantics: keys
    are fold_in(fold_in(PRNGKey(seed), stream_pos), step), where
    stream_pos is the scheduler's persistent position — advanced here by
    the supersteps consumed — so repeated run()/step() calls keep drawing
    fresh samples (and the legacy engine shim's per-call reset() restores
    the historical restart).  Within a run the trajectory is invariant to
    steps_per_sync (superstep t draws the same key regardless of
    chunking), so tile_loads/supersteps are identical across cadences."""
    if getattr(sess, "_mesh2d", None) is not None:
        from repro.dist.mesh2d import run_device_2d
        return run_device_2d(policy, sess, max_supersteps)
    groups = sess.view_groups()
    step_fn = sess._device_step_fn(policy)
    tel_cfg = getattr(sess, "telemetry", None)
    tel_cap = int(tel_cfg.capacity) if tel_cfg is not None else 0
    trace = getattr(sess, "trace", None)
    trace = trace if trace is not None and trace.enabled else None
    # the budget the device compares against must be the SAME clamped
    # value the host loop tests, or a >int32 budget could spin forever
    budget = int(min(max_supersteps, np.iinfo(np.int32).max))
    state, scales, tiles, nbrs, ovs, prs, max_steps, key = \
        device_step_args(sess, budget, sess._consume_dirty_boost())
    m = RunMetrics()
    while True:
        t_chunk = trace.now_us() if trace else 0.0
        with _profiler_span(sess, "device_chunk"):
            state, un = step_fn(state, scales, tiles, nbrs, ovs, prs,
                                max_steps, key)
            # the ONE host sync of the chunk: explicit, batched, and the
            # only transfer a transfer_guard("disallow") run will see
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
    loads_h, pushes_h, pair_loads_h, iters_h = jax.device_get(
        (state[3], state[4], state[5], state[6]))
    m.tile_loads = int(loads_h)
    m.job_block_pushes = int(pushes_h)
    m.tile_pair_loads = int(pair_loads_h)
    m.converged = un_h == 0
    m.iterations_per_job = np.concatenate(
        [np.asarray(x, dtype=np.int64) for x in iters_h])
    if tel_cap:
        m.telemetry = series_from_device(state[8], it_h,
                                         [g.key for g in groups])
    return m


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------


def _group_queues_device(nu, pm, key, gi, q, samples):
    """One view group's DO queues on device: per-job sampling keys derived
    fold_in(superstep key, group index) then split over the job axis, so
    every (policy, group, job, step) draws from one reproducible stream."""
    keys = jax.random.split(jax.random.fold_in(key, gi), nu.shape[0])
    return jax.vmap(
        lambda n, p, k: do_select_device(n, p, q, k, samples))(nu, pm, keys)


class TwoLevel(SchedulePolicy):
    """The paper's schedule: MPDS (DO queues + global queue) + CAJS push.

    The global queue is synthesized across ALL jobs' DO queues regardless
    of view (block ids are view-agnostic); one staging of each selected
    block then serves both semiring families in the same superstep.  With
    backend="device" both levels run inside the jitted superstep: per-job
    do_select_device sampling feeds one weighted scatter-add synthesis
    with reserved head slots."""

    name = "two_level"

    def select(self, sess, node_un, p_mean, active):
        sched = sess.scheduler
        queues = []
        for nu, pm, act in zip(node_un, p_mean, active):
            queues.extend(sched.job_queues(nu, pm, act))
        gq = sched.synthesize(queues)
        if len(gq) == 0:
            return None
        q = sess.q
        # metrics honesty: only the staged prefix counts (synthesize also
        # asserts len(gq) <= q, so this clamp is a guard, not a behaviour)
        gq = gq[:q]
        sel = np.zeros(q, dtype=np.int32)
        msk = np.zeros(q, dtype=np.float32)
        sel[:len(gq)] = gq
        msk[:len(gq)] = 1.0
        # CAJS: staged once, dispatched only to jobs unconverged on the block
        pushes = sum(int((nu[:, gq] > 0).sum()) for nu in node_un)
        return Selection(sel, msk, shared=True, tile_loads=int(len(gq)),
                         job_block_pushes=pushes)

    def device_select(self, node_uns, p_means, actives, key, *, q, alpha,
                      samples, num_blocks):
        pri = jnp.zeros((num_blocks,), jnp.float32)
        heads = jnp.zeros((num_blocks,), jnp.bool_)
        for gi, (nu, pm) in enumerate(zip(node_uns, p_means)):
            sel, msk = _group_queues_device(nu, pm, key, gi, q, samples)
            pri, heads = accumulate_priority(pri, heads, sel, msk, q)
        gsel, gmsk = synthesize_topq(pri, heads, q, alpha)
        # dtype contract (see Selection): per-step counters are int32; the
        # drivers accumulate in float32, which only rounds totals >2^24
        pushes = jnp.int32(0)
        for nu in node_uns:
            pushes = pushes + jnp.sum(
                ((nu[:, gsel] > 0) & (gmsk > 0)[None, :])
                .astype(jnp.int32))
        return Selection(gsel, gmsk, shared=True,
                         tile_loads=jnp.sum(gmsk > 0).astype(jnp.int32),
                         job_block_pushes=pushes)


class Independent(SchedulePolicy):
    """Per-job queues processed separately (paper Fig. 3 'current mode')."""

    name = "independent"

    def select(self, sess, node_un, p_mean, active):
        q = sess.q
        sels, msks = [], []
        loads = pushes = 0
        for nu, pm, act in zip(node_un, p_mean, active):
            j_cap = nu.shape[0]
            sel = np.zeros((j_cap, q), dtype=np.int32)
            msk = np.zeros((j_cap, q), dtype=np.float32)
            for j, qj in enumerate(sess.scheduler.job_queues(nu, pm, act)):
                if len(qj) == 0:
                    continue
                sel[j, :len(qj)] = qj[:q]
                msk[j, :len(qj)] = 1.0
                loads += int(len(qj))          # each job stages its own
                pushes += int(len(qj))
            sels.append(sel)
            msks.append(msk)
        return Selection(sels, msks, shared=False, tile_loads=loads,
                         job_block_pushes=pushes)

    def device_select(self, node_uns, p_means, actives, key, *, q, alpha,
                      samples, num_blocks):
        sels, msks = [], []
        loads = jnp.int32(0)
        for gi, (nu, pm) in enumerate(zip(node_uns, p_means)):
            sel, msk = _group_queues_device(nu, pm, key, gi, q, samples)
            sels.append(sel)
            msks.append(msk)
            loads = loads + jnp.sum(msk > 0).astype(jnp.int32)
        return Selection(sels, msks, shared=False, tile_loads=loads,
                         job_block_pushes=loads)


class AllBlocks(SchedulePolicy):
    """Non-prioritized synchronous baseline: all blocks, shared staging."""

    name = "all_blocks"
    needs_pairs = False

    def select(self, sess, node_un, p_mean, active):
        bn = sess.scheduler.num_blocks
        sel = np.arange(bn, dtype=np.int32)
        msk = np.ones(bn, dtype=np.float32)
        n_active = sum(int(a.sum()) for a in active)
        return Selection(sel, msk, shared=True, tile_loads=bn,
                         job_block_pushes=bn * n_active)

    def device_select(self, node_uns, p_means, actives, key, *, q, alpha,
                      samples, num_blocks):
        n_active = jnp.int32(0)
        for act in actives:
            n_active = n_active + jnp.sum(act.astype(jnp.int32))
        return Selection(jnp.arange(num_blocks, dtype=jnp.int32),
                         jnp.ones(num_blocks, jnp.float32), shared=True,
                         tile_loads=jnp.int32(num_blocks),
                         job_block_pushes=jnp.int32(num_blocks) * n_active)


class Fused(TwoLevel):
    """Beyond-paper alias: TwoLevel(backend="device", steps_per_sync=inf).

    The entire two-level loop — priority pairs, per-job DO sampling,
    global synthesis, push, convergence test — is one on-device
    lax.while_loop with no host round-trips until the fixpoint.  Its
    historical dedicated run() fork is gone: this class only pins the
    backend; pass a finite steps_per_sync to trade convergence-latency
    for mid-batch submit/detach opportunities."""

    name = "fused"

    def __init__(self, *, steps_per_sync: Union[int, float] = math.inf):
        super().__init__(backend=DEVICE, steps_per_sync=steps_per_sync)


POLICIES = {p.name: p for p in (TwoLevel, Fused, Independent, AllBlocks)}
