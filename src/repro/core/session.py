"""GraphSession: a long-lived job-lifecycle API over one shared graph.

The paper's premise is massive concurrent jobs ARRIVING AND LEAVING while
sharing one graph (its §4.4 API has `initPtable` for a "newly-arrived
job"), yet the historical engine API only ran a fixed job set to a joint
fixpoint.  A GraphSession owns the shared graph data and exposes:

  submit(alg) -> JobHandle     admit a job at ANY superstep
  run(policy, max_supersteps)  advance all active jobs under a SchedulePolicy
  step(policy)                 a single superstep
  converged(handle)            per-job convergence test
  result(handle)               per-job result extraction
  detach(handle)               release the job's slot for reuse

Sessions are HETEROGENEOUS: jobs from both semiring families (PageRank/
PPR/Katz under plus-times, SSSP/BFS/WCC under min-plus) coexist over one
shared CSR.  Internally the session keeps a registry of ViewGroups, one
per graph-view key `(semiring, fill, normalize, symmetrize)`.  Each view's
BlockedGraph is derived lazily from the shared CSR with the SAME block
size, so block id b names the same vertex range in every view — which is
what lets one scheduling decision (a set of block ids) drive every family
at once: the paper's CAJS staging of block b serves the plus-times push
and the min-plus push in the same superstep, and `RunMetrics.tile_loads`
counts that staging once.

Each group maintains a PADDED [J_view_cap, B_N, Vb] job axis plus an
active mask, so jitted push shapes stay stable across arrivals/departures:
free slots hold the semiring's inert state (delta 0 / +inf), which makes
them arithmetic no-ops in every policy — no re-tracing on submit/detach.
Slots are recycled; handle generations catch stale use.  A group's
capacity doubles (one re-trace) only when submissions exceed it.

`run(..., mesh=...)` composes any policy with job-axis placement from
repro.dist.graph (every view's tiles replicated, every group's job state
sharded over its own job axis).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.algorithms.base import Algorithm, PLUS_TIMES
from repro.core.policy import RunMetrics, SchedulePolicy, TwoLevel
from repro.core.push import (compute_pairs, indep_push_fn, push_plus_one,
                             push_min_one, shared_push_fn)
from repro.core.scheduler import (TwoLevelScheduler, optimal_queue_length,
                                  PRITER_C)
from repro.core.do_select import DEFAULT_SAMPLES
from repro.core.global_q import DEFAULT_ALPHA
from repro.graph.structure import (BlockedGraph, CSRGraph, TileOverlay,
                                   build_blocked, empty_overlay)
from repro.obs.telemetry import TelemetryConfig
from repro.obs.trace import TraceRecorder, count, span


@dataclasses.dataclass(frozen=True)
class JobHandle:
    """Ticket for a submitted job; stale after detach (generation check)."""

    slot: int
    gen: int
    alg: Algorithm
    view: Optional[tuple] = None   # graph-view key; derived from alg if None


def _view_key(alg: Algorithm) -> tuple:
    return (alg.semiring, alg.graph_fill, alg.graph_normalize,
            alg.graph_symmetrize)


@dataclasses.dataclass
class ViewGroup:
    """One graph view + the padded job axis of every job using it.

    `alg` is the view's exemplar (the first job submitted into it): it
    supplies the pair computation / convergence test / inert fill for the
    whole group, exactly as the pre-heterogeneous session used its first
    submitted algorithm.  All jobs in a group share the semiring by
    construction (the semiring is part of the view key).
    """

    key: tuple
    alg: Algorithm
    graph: BlockedGraph
    push_one: Callable
    values: jnp.ndarray       # [cap, B_N, Vb]
    deltas: jnp.ndarray       # [cap, B_N, Vb]
    push_scale: jnp.ndarray   # [cap]
    algs: List[Optional[Algorithm]]
    active: np.ndarray        # [cap] bool
    gens: List[int]
    # evolving-graph state (repro.stream): the bounded per-block delta-COO
    # staged alongside the tiles (capacity 0 until the first structural
    # insert), plus host mirrors of the blocked structure that
    # apply_updates needs to classify edits — built lazily on first use
    overlay: Optional[TileOverlay] = None
    pair_slot: Optional[Dict] = None   # {(src block, dst block): slot}
    ov_used: Optional[np.ndarray] = None   # [B_N, C] bool
    ov_entry: Optional[Dict] = None    # {(u, v) padded ids: (block, col)}
    # destination-sorted sparse block-pair view of `graph` (the fused
    # megakernel's adjacency + the real-bytes tile_pair_loads accounting)
    # — built lazily by session._pair_data, dropped to None whenever the
    # tiles change (stream structural edits, compaction)
    pairs: Optional[object] = None
    # dst-partitioned PairShards of `pairs` for a 2D (jobs x blocks) mesh
    # (repro.dist.mesh2d), cached as (source BlockPairs, mesh signature,
    # placed shards) — the strong reference makes the identity check safe
    # and a rebuild of `pairs` (compaction) auto-invalidates the partition
    pair_shards: Optional[tuple] = None

    @property
    def capacity(self) -> int:
        return len(self.algs)

    @property
    def semiring(self) -> str:
        return self.key[0]

    @property
    def num_active(self) -> int:
        return int(self.active.sum())


def _inert_fill(semiring: str) -> float:
    return 0.0 if semiring == PLUS_TIMES else float("inf")


def _inert_state(semiring: str, g: BlockedGraph, n: int):
    """State for free slots: converged-everywhere, pushes are no-ops."""
    fill = _inert_fill(semiring)
    shape = (n, g.num_blocks, g.block_size)
    return (jnp.full(shape, fill, dtype=jnp.float32),
            jnp.full(shape, fill, dtype=jnp.float32))


# -- slot programs: a job's admission and retirement, one dispatch each ------
#
# Each is compiled once per (view, algorithm class and static fields): the
# slot and the job's own numbers (`Algorithm.job_fields`) are traced
# arguments, so every slot and every source shares one compilation.

def _job_args(alg: Algorithm) -> tuple:
    """The job's own numbers as arguments of its slot programs: numpy
    values, so every job of a class traces to the same signature."""
    return tuple(np.asarray(getattr(alg, f)) for f in alg.job_fields)


def _with_job(alg: Algorithm, job: tuple) -> Algorithm:
    return dataclasses.replace(alg, **dict(zip(alg.job_fields, job)))


def _admit_program(alg: Algorithm):
    """The job's state from its own `init`, and its push scale, written
    into `slot` of the view's job axis."""
    def admit(values, deltas, push_scale, slot, job, graph):
        a = _with_job(alg, job)
        v, d = a.init(graph)
        return (values.at[slot].set(v), deltas.at[slot].set(d),
                push_scale.at[slot].set(a.get_push_scale()))
    return jax.jit(admit)


def _retire_program(alg: Algorithm, fill: float):
    """The job's result, with `slot` reset to the inert state."""
    def retire(values, deltas, push_scale, slot, job):
        res = _with_job(alg, job).result(values[slot], deltas[slot])
        return (res, values.at[slot].set(fill), deltas.at[slot].set(fill),
                push_scale.at[slot].set(1.0))
    return jax.jit(retire)


def _dispatch(program, *args):
    """One call of a slot program; counts it, and each compilation it
    caused (a new program, shape or placement)."""
    n = program._cache_size()
    out = program(*args)
    count("slot_programs", 1)
    count("slot_compiles", program._cache_size() - n)
    return out


def _store(grp: ViewGroup, values, deltas, push_scale) -> None:
    """A slot program's state into `grp`, on the placement the group's
    state had: the compiled program may pick an equivalent sharding of its
    own (a size-1 mesh axis dropped), which the next superstep would
    compile for again."""
    def placed(new, old):
        return (new if new.sharding == old.sharding
                else jax.device_put(new, old.sharding))
    grp.values = placed(values, grp.values)
    grp.deltas = placed(deltas, grp.deltas)
    grp.push_scale = placed(push_scale, grp.push_scale)


class GraphSession:
    """Owns the shared graph data + per-view padded, recyclable job axes."""

    def __init__(self, csr: Optional[CSRGraph] = None, block_size: int = 64,
                 *, capacity: int = 4, c: float = PRITER_C,
                 alpha: float = DEFAULT_ALPHA, samples: int = DEFAULT_SAMPLES,
                 seed: int = 0, use_pallas: bool = False,
                 overlay_capacity: int = 32, telemetry=None):
        self._csr = csr
        # observability (repro.obs): telemetry=True / TelemetryConfig(...)
        # turns on per-superstep series + trace recording; None/False (the
        # default) compiles the exact pre-observability programs
        self.telemetry: Optional[TelemetryConfig] = \
            TelemetryConfig.coerce(telemetry)
        self.trace = TraceRecorder(
            enabled=self.telemetry is not None and self.telemetry.trace)
        self.trace.name_thread(2, "supersteps")
        self.block_size = block_size
        self._capacity0 = max(1, int(capacity))   # initial per-view capacity
        self.c = c
        self._alpha = alpha
        self._samples = samples
        self._seed = seed
        self.use_pallas = use_pallas
        # evolving graphs (repro.stream): per-block delta-COO budget a view
        # grows to on its first structural insert; a full block row triggers
        # compaction (BlockedGraph rebuilt from the updated CSR)
        self.overlay_capacity = max(1, int(overlay_capacity))
        self._dirty_boost: Optional[np.ndarray] = None  # [B_N] pending boost
        self._stream_pending = {"updates_applied": 0, "dirty_blocks": 0,
                                "reseed_num": 0, "reseed_den": 0}
        # view registry, populated lazily on submit (insertion-ordered; the
        # order defines the concatenated job-metric layout, see job_index)
        self.groups: Dict[tuple, ViewGroup] = {}
        self.scheduler: Optional[TwoLevelScheduler] = None
        self.q = 0
        self._jit_cache = {}
        # 2D (jobs x blocks) mesh placement (repro.dist.mesh2d.Mesh2DSpec)
        # or None; set by shard_session_2d, cleared by unshard_session —
        # reroutes the device superstep and the push functions while set
        self._mesh2d = None

    # alpha/samples/seed live canonically on the scheduler once it exists
    # (every policy must see one consistent value); before the first submit
    # they are held locally

    @property
    def alpha(self) -> float:
        return self.scheduler.alpha if self.scheduler else self._alpha

    @alpha.setter
    def alpha(self, value: float) -> None:
        self._alpha = value
        if self.scheduler:
            self.scheduler.alpha = value

    @property
    def samples(self) -> int:
        return self.scheduler.samples if self.scheduler else self._samples

    @samples.setter
    def samples(self, value: int) -> None:
        self._samples = value
        if self.scheduler:
            self.scheduler.samples = value

    @property
    def seed(self) -> int:
        return self.scheduler.seed if self.scheduler else self._seed

    @seed.setter
    def seed(self, value: int) -> None:
        self._seed = value
        if self.scheduler:
            self.scheduler.reset(value)  # re-seeds AND restarts the stream

    # -- view registry -------------------------------------------------------

    def view_groups(self) -> List[ViewGroup]:
        """All view groups in creation order (the metric layout order)."""
        return list(self.groups.values())

    @property
    def total_capacity(self) -> int:
        return sum(g.capacity for g in self.groups.values())

    @property
    def capacity(self) -> int:
        """Total padded slots across views (initial capacity pre-submit)."""
        return self.total_capacity if self.groups else self._capacity0

    def _sole_group(self) -> ViewGroup:
        if len(self.groups) != 1:
            raise ValueError(
                f"session holds {len(self.groups)} graph views; "
                "per-view state has no single values/deltas/graph — use "
                "view_groups()")
        return next(iter(self.groups.values()))

    # single-view compatibility surface (the legacy engine shim and all
    # homogeneous callers): delegates to the one group

    @property
    def graph(self):
        return next(iter(self.groups.values())).graph if self.groups else None

    @property
    def view_alg(self) -> Optional[Algorithm]:
        return next(iter(self.groups.values())).alg if self.groups else None

    @property
    def values(self):
        return self._sole_group().values

    @values.setter
    def values(self, v) -> None:
        self._sole_group().values = v

    @property
    def deltas(self):
        return self._sole_group().deltas

    @deltas.setter
    def deltas(self, d) -> None:
        self._sole_group().deltas = d

    @property
    def push_scale(self):
        return self._sole_group().push_scale

    @push_scale.setter
    def push_scale(self, p) -> None:
        self._sole_group().push_scale = p

    # -- construction from a legacy ConcurrentRun ---------------------------

    @classmethod
    def from_run(cls, run, *, c: float = PRITER_C,
                 alpha: float = DEFAULT_ALPHA,
                 samples: int = DEFAULT_SAMPLES, seed: int = 0,
                 use_pallas: bool = False) -> "GraphSession":
        """Adopt a pre-built ConcurrentRun: one view, capacity == J, no
        padding, so the legacy engine shim stays bit-identical to the
        historical API."""
        sess = cls(None, run.graph.block_size, capacity=run.num_jobs,
                   c=c, alpha=alpha, samples=samples, seed=seed,
                   use_pallas=use_pallas)
        a0 = run.algs[0]
        sess._install_scheduler(run.graph)
        sess.groups[_view_key(a0)] = ViewGroup(
            key=_view_key(a0), alg=a0, graph=run.graph,
            push_one=(push_plus_one if a0.semiring == PLUS_TIMES
                      else push_min_one),
            values=run.values, deltas=run.deltas, push_scale=run.push_scale,
            algs=list(run.algs),
            active=np.ones(run.num_jobs, dtype=bool),
            gens=[0] * run.num_jobs,
            overlay=empty_overlay(run.graph.num_blocks))
        return sess

    # -- graph / scheduler initialisation ------------------------------------

    def _install_scheduler(self, g: BlockedGraph) -> None:
        """First view sets q + the scheduler; later views must be
        block-aligned (same B_N ⇒ block id b names the same vertex range in
        every view), which same-n/same-block-size construction guarantees."""
        if self.scheduler is None:
            self.q = optimal_queue_length(g.num_blocks, g.n_real, self.c)
            self.scheduler = TwoLevelScheduler(
                g.num_blocks, self.q, alpha=self.alpha, samples=self.samples,
                seed=self.seed)
        elif g.num_blocks != self.scheduler.num_blocks:
            raise ValueError(
                f"view is not block-aligned: {g.num_blocks} blocks != "
                f"{self.scheduler.num_blocks}")

    def _group_for(self, alg: Algorithm) -> ViewGroup:
        key = _view_key(alg)
        grp = self.groups.get(key)
        if grp is not None:
            return grp
        if self._csr is None:
            raise ValueError("GraphSession needs a CSRGraph to build from")
        g_csr = (self._csr.symmetrized() if alg.graph_symmetrize
                 else self._csr)
        g = build_blocked(g_csr, self.block_size, fill=alg.graph_fill,
                          normalize=alg.graph_normalize)
        self._install_scheduler(g)
        cap = self._capacity0
        values, deltas = _inert_state(alg.semiring, g, cap)
        grp = ViewGroup(
            key=key, alg=alg, graph=g,
            push_one=(push_plus_one if alg.semiring == PLUS_TIMES
                      else push_min_one),
            values=values, deltas=deltas,
            push_scale=jnp.ones(cap, dtype=jnp.float32),
            algs=[None] * cap, active=np.zeros(cap, dtype=bool),
            gens=[0] * cap,
            overlay=empty_overlay(g.num_blocks))
        self.groups[key] = grp
        return grp

    def _grow(self, grp: ViewGroup) -> None:
        extra = grp.capacity
        iv, idl = _inert_state(grp.semiring, grp.graph, extra)
        grp.values = jnp.concatenate([grp.values, iv])
        grp.deltas = jnp.concatenate([grp.deltas, idl])
        grp.push_scale = jnp.concatenate(
            [grp.push_scale, jnp.ones(extra, dtype=jnp.float32)])
        grp.algs.extend([None] * extra)
        grp.gens.extend([0] * extra)
        grp.active = np.concatenate(
            [grp.active, np.zeros(extra, dtype=bool)])

    # -- job lifecycle -------------------------------------------------------

    @property
    def num_active(self) -> int:
        return sum(g.num_active for g in self.groups.values())

    def submit(self, alg: Algorithm) -> JobHandle:
        """Admit a job at any superstep; recycles a free slot or grows its
        view group.  Jobs of a NEW graph view build that view lazily from
        the shared CSR and coexist with every already-running family."""
        with span("session.submit", self.trace, cat="job",
                  alg=type(alg).__name__) as s:
            grp = self._group_for(alg)
            free = np.nonzero(~grp.active)[0]
            if len(free) == 0:
                self._grow(grp)
                free = np.nonzero(~grp.active)[0]
            slot = int(free[0])
            s.set(view=grp.key, slot=slot, gen=grp.gens[slot])
            admit = self._slot_program("admit", grp, alg)
            with span("session.submit.program"):
                _store(grp, *_dispatch(
                    admit, grp.values, grp.deltas, grp.push_scale,
                    np.int32(slot), _job_args(alg), grp.graph))
            grp.algs[slot] = alg
            grp.active[slot] = True
        return JobHandle(slot=slot, gen=grp.gens[slot], alg=alg, view=grp.key)

    def _handle_group(self, handle: JobHandle) -> ViewGroup:
        key = handle.view if handle.view is not None else _view_key(handle.alg)
        grp = self.groups.get(key)
        if grp is None or not (0 <= handle.slot < grp.capacity) \
                or grp.gens[handle.slot] != handle.gen \
                or not grp.active[handle.slot]:
            raise KeyError(f"stale or unknown job handle {handle}")
        return grp

    def job_index(self, handle: JobHandle) -> int:
        """Index of this job in the concatenated per-group layout used by
        `unconverged_counts()` and `RunMetrics.iterations_per_job` (view
        groups in creation order, slots within a group).  For a single-view
        session this equals `handle.slot`."""
        grp = self._handle_group(handle)
        off = 0
        for g in self.groups.values():
            if g is grp:
                return off + handle.slot
            off += g.capacity
        raise KeyError(f"unknown view for handle {handle}")

    def unconverged_counts(self) -> np.ndarray:
        """[total_capacity] unconverged-vertex count per slot, view groups
        concatenated in creation order (0 for free slots) — one device
        reduction per view; index by `job_index(handle)` to poll many
        handles (== handle.slot for single-view sessions)."""
        with span("session.poll"):
            parts = []
            for g in self.groups.values():
                parts.append(jax.device_get(
                    self._counts_fn(g)(g.values, g.deltas)))
                count("device_reads", 1)
        return (np.concatenate(parts) if parts
                else np.zeros(0, dtype=np.int32))

    def converged(self, handle: JobHandle) -> bool:
        grp = self._handle_group(handle)
        counts = jax.device_get(self._counts_fn(grp)(grp.values, grp.deltas))
        count("device_reads", 1)
        return bool(counts[handle.slot] == 0)

    def result(self, handle: JobHandle) -> np.ndarray:
        """[n_real] result for one job (valid at any superstep)."""
        grp = self._handle_group(handle)
        res = handle.alg.result(grp.values[handle.slot],
                                grp.deltas[handle.slot])
        out = jax.device_get(res)
        count("device_reads", 1)
        return out.reshape(-1)[:grp.graph.n_real]

    def detach(self, handle: JobHandle) -> np.ndarray:
        """Extract the job's result and free its slot for reuse."""
        with span("session.detach", self.trace, cat="job",
                  alg=type(handle.alg).__name__, view=handle.view,
                  slot=handle.slot, gen=handle.gen):
            grp = self._handle_group(handle)
            slot = handle.slot
            retire = self._slot_program("retire", grp, handle.alg)
            with span("session.detach.program"):
                res, *state = _dispatch(
                    retire, grp.values, grp.deltas, grp.push_scale,
                    np.int32(slot), _job_args(handle.alg))
                _store(grp, *state)
            with span("session.detach.read"):
                out = jax.device_get(res)
            count("device_reads", 1)
            grp.algs[slot] = None
            grp.active[slot] = False
            grp.gens[slot] += 1
        return out.reshape(-1)[:grp.graph.n_real]

    # -- evolving graphs (repro.stream) --------------------------------------

    def apply_updates(self, batch) -> "RunMetrics":
        """Apply a live edge insert/delete/reweight batch while jobs run.

        The shared CSR is the source of truth: the batch updates it
        exactly, then every view group absorbs the change — in-place tile
        edits for block pairs that own a tile slot, the bounded per-block
        delta-COO overlay for structurally-new pairs (a full overlay row
        compacts the view: BlockedGraph rebuilt from the updated CSR,
        bit-identical to a from-scratch build) — and every job's state is
        invalidated just enough to converge to the NEW graph's fixpoint
        (see repro.stream.invalidate).  Affected blocks are remembered and
        injected as priority boosts into the next run()'s DO queues, so
        the two-level scheduler prioritizes update-affected data for all
        concurrent jobs at once.  Callable at any superstep between
        run()/step() calls; returns the accumulated stream counters (also
        drained into the next run()'s RunMetrics)."""
        from repro.stream.apply import apply_updates_to_session
        return apply_updates_to_session(self, batch)

    def compact(self) -> None:
        """Force compaction of every view: rebuild each BlockedGraph from
        the updated CSR (bit-identical to a from-scratch build) and empty
        the overlays.  Happens automatically when an overlay row fills."""
        from repro.stream.apply import compact_group
        if self._csr is None:
            raise ValueError(
                "compact needs the session-owned CSRGraph (sessions "
                "adopted from a legacy ConcurrentRun have none)")
        for grp in self.view_groups():
            compact_group(self, grp)

    def _consume_dirty_boost(self) -> Optional[np.ndarray]:
        """[B_N] pending priority injection for update-affected blocks, or
        None; consumed by the first superstep of the next run."""
        boost, self._dirty_boost = self._dirty_boost, None
        return boost

    def _drain_stream_stats(self, metrics) -> None:
        p = self._stream_pending
        metrics.updates_applied = p["updates_applied"]
        metrics.dirty_blocks = p["dirty_blocks"]
        metrics.reseed_fraction = (p["reseed_num"] / p["reseed_den"]
                                   if p["reseed_den"] else 0.0)
        self._stream_pending = {"updates_applied": 0, "dirty_blocks": 0,
                                "reseed_num": 0, "reseed_den": 0}

    # -- jitted primitives (shared by every policy), cached per view ---------

    def _device_step_fn(self, policy):
        """Compiled device superstep for `policy`, cached on the session.

        Keyed on everything that shapes the traced program: the policy's
        selection code (the `device_select` function itself plus
        needs_pairs, so `Fused()` and the literal
        `TwoLevel(backend="device", steps_per_sync=inf)` share one
        compilation while a subclass overriding `device_select` gets its
        own), steps_per_sync, the view keys (which algs/semirings
        participate), per-view capacities (array shapes), q, alpha,
        samples, the pallas toggle and the telemetry capacity (0 when
        off, so a telemetry-off session compiles the exact
        pre-observability program and an on/off pair never shares — or
        invalidates — a cache entry).  Repeated run() calls,
        submit/detach cycles at unchanged capacity, and re-placement on a
        mesh all REUSE the same compilation (jax re-specializes on
        shardings internally); only a genuinely new program shape — a new
        view, a capacity doubling, a different sync cadence — compiles
        again."""
        from repro.core.policy import build_device_step
        groups = self.view_groups()
        tel_cap = self.telemetry.capacity if self.telemetry else 0
        key = ("superstep", type(policy).device_select, policy.needs_pairs,
               policy.steps_per_sync,
               tuple(g.key for g in groups),
               tuple(g.capacity for g in groups),
               tuple(g.overlay.capacity for g in groups),
               self.q, float(self.alpha), int(self.samples),
               self.use_pallas, tel_cap)
        if self._mesh2d is not None:
            # the 2D superstep closes over the mesh layout AND the pair
            # partition's shapes (the shard_map in_specs pytrees), so both
            # join the key; leaving the mesh falls back to the 1D entry —
            # one entry per (policy, shape, placement), never growth per
            # run() (pinned by tests/test_dist_mesh2d.py retrace test)
            from repro.dist.mesh2d import build_device_step_2d
            key = key + (self._mesh2d.signature(),
                         tuple(self._pair_shards(g).tree_flatten()[1]
                               for g in groups))
            if key not in self._jit_cache:
                self._jit_cache[key] = build_device_step_2d(
                    policy, self, self._mesh2d)
            return self._jit_cache[key]
        if key not in self._jit_cache:
            self._jit_cache[key] = build_device_step(policy, self)
        return self._jit_cache[key]

    def _slot_program(self, kind: str, grp: ViewGroup, alg: Algorithm):
        """The view's "admit" or "retire" program for `alg`'s class and
        static fields (its job fields cleared), built on first use."""
        static = dataclasses.replace(alg, **dict.fromkeys(alg.job_fields))
        key = (kind, grp.key, static)
        if key not in self._jit_cache:
            self._jit_cache[key] = (
                _admit_program(static) if kind == "admit"
                else _retire_program(static, _inert_fill(grp.semiring)))
        return self._jit_cache[key]

    def _pairs_fn(self, grp: ViewGroup, with_resid: bool = False):
        """with_resid=True additionally returns the group's max vertex
        priority (the telemetry residual) from the SAME jitted program —
        telemetry must not add a device dispatch per superstep."""
        key = ("pairs", grp.key, with_resid)
        if key not in self._jit_cache:
            alg = grp.alg
            if with_resid:
                self._jit_cache[key] = jax.jit(
                    lambda v, d: (*compute_pairs(alg, v, d),
                                  jnp.max(alg.vertex_priority(v, d))))
            else:
                self._jit_cache[key] = jax.jit(
                    lambda v, d: compute_pairs(alg, v, d))
        return self._jit_cache[key]

    def _counts_fn(self, grp: ViewGroup, with_resid: bool = False):
        key = ("counts", grp.key, with_resid)
        if key not in self._jit_cache:
            alg = grp.alg
            if with_resid:
                self._jit_cache[key] = jax.jit(
                    lambda v, d: (jnp.sum(alg.unconverged(v, d),
                                          axis=(1, 2)),
                                  jnp.max(alg.vertex_priority(v, d))))
            else:
                self._jit_cache[key] = jax.jit(
                    lambda v, d: jnp.sum(alg.unconverged(v, d),
                                         axis=(1, 2)))
        return self._jit_cache[key]

    def _pair_data(self, grp: ViewGroup):
        """The view's destination-sorted `BlockPairs`, built lazily from
        the CURRENT tiles and cached on the group; stream structural
        edits / compaction invalidate it (set `grp.pairs = None`) so the
        next run rebuilds from the edited tiles."""
        if grp.pairs is None:
            from repro.graph.structure import build_block_pairs
            grp.pairs = build_block_pairs(grp.graph)
        return grp.pairs

    def _push_shared_fn(self, grp: ViewGroup):
        """All jobs of the view process the same selected blocks (CAJS)."""
        if self._mesh2d is not None:
            key = ("push_shared2d", grp.key, self.use_pallas,
                   self._mesh2d.signature())
            if key not in self._jit_cache:
                from repro.dist.mesh2d import shared_push_fn_2d
                self._jit_cache[key] = shared_push_fn_2d(
                    self._mesh2d, grp, self.use_pallas)
            return self._jit_cache[key]
        key = ("push_shared", grp.key, self.use_pallas)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(shared_push_fn(
                grp.semiring, grp.push_one, self.use_pallas))
        return self._jit_cache[key]

    def _push_indep_fn(self, grp: ViewGroup):
        """Each job processes its own selection (redundancy baseline)."""
        if self._mesh2d is not None:
            key = ("push_indep2d", grp.key, self._mesh2d.signature())
            if key not in self._jit_cache:
                from repro.dist.mesh2d import indep_push_fn_2d
                self._jit_cache[key] = indep_push_fn_2d(self._mesh2d, grp)
            return self._jit_cache[key]
        key = ("push_indep", grp.key)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(indep_push_fn(grp.push_one))
        return self._jit_cache[key]

    def _pair_shards(self, grp: ViewGroup):
        """The view's dst-partitioned `PairShards` on the current 2D mesh
        (repro.dist.mesh2d), cached on the group against the identity of
        the source BlockPairs and the mesh signature — compaction rebuilds
        `grp.pairs`, so the partition follows automatically; blocks-
        replicated groups get the trivial 1-shard partition."""
        from repro.dist.mesh2d import (partition_block_pairs,
                                       place_pair_shards)
        spec = self._mesh2d
        bp = self._pair_data(grp)
        lay = spec.layout(grp)
        n = spec.block_shards if lay.blocks_sharded else 1
        cached = grp.pair_shards
        if (cached is not None and cached[0] is bp
                and cached[1] == spec.signature()):
            return cached[2]
        fill = float(grp.alg.graph_fill)
        ps = place_pair_shards(spec, partition_block_pairs(bp, n, fill),
                               lay.blocks_sharded)
        grp.pair_shards = (bp, spec.signature(), ps)
        return ps

    # -- placement -----------------------------------------------------------

    def _place(self, mesh) -> None:
        """Shard every view group's job axis over `mesh` (repro.dist.graph):
        each view's tiles replicated per device, its values/deltas
        job-sharded.  Scheduling is unchanged — SPMD partitions the vmapped
        pushes along each job axis, so per-job arithmetic (and the fixpoint)
        is identical."""
        if mesh is None:
            return
        # a mesh with >= 2 named axes selects the 2D (jobs x blocks)
        # placement; shard_session also clears a previous 2D placement
        # when re-placing on a 1D mesh
        from repro.dist.graph import shard_session
        shard_session(mesh, self)

    # -- driving -------------------------------------------------------------

    def run(self, policy: Optional[SchedulePolicy] = None,
            max_supersteps: int = 100000, *, mesh=None) -> RunMetrics:
        """Advance all active jobs until they converge (or the budget ends).

        Jobs submitted after this returns resume from the shared state:
        call run() again to drive the new mix — that is the arrival model.
        Under a device-backend policy with steps_per_sync=K the session
        only regains control every K supersteps, so an arrival waits up to
        K supersteps before the next run() can admit it (see docs/API.md,
        "Scheduler backends")."""
        if not self.groups:
            raise ValueError("no jobs submitted yet")
        policy = TwoLevel() if policy is None else policy
        self._place(mesh)
        with span("session.run", self.trace, cat="run",
                  policy=policy.name) as s:
            t_run = self.trace.now_us() if self.trace.enabled else 0.0
            m = policy.run(self, max_supersteps)
            self._drain_stream_stats(m)
            if self.trace.enabled:
                s.set(**m.to_dict())
        if self.trace.enabled:
            self._trace_run(m, t_run, self.trace.now_us() - t_run)
        return m

    def _trace_run(self, m: RunMetrics, t_run: float, dur: float) -> None:
        """Counter tracks from the telemetry series, across the run span."""
        if m.converged:
            self.trace.instant("converged", cat="run",
                               supersteps=int(m.supersteps))
        tel = m.telemetry
        if tel is None or len(tel) == 0:
            return
        # counter samples interpolated across the run span (the device
        # backend has no per-superstep wall clock); stride caps the event
        # volume for very long runs
        k = len(tel)
        stride = max(1, k // 2000)
        for i in range(0, k, stride):
            ts = t_run + dur * (i + 1) / k
            vals = {"active_jobs": int(tel.active_jobs[i]),
                    "tile_loads": int(tel.tile_loads[i]),
                    "job_block_pushes": int(tel.job_block_pushes[i]),
                    "gq_occupancy": int(tel.gq_occupancy[i]),
                    "dirty_blocks": int(tel.dirty_blocks[i]),
                    "tile_pair_loads": int(tel.tile_pair_loads[i]),
                    "halo_bytes": float(tel.halo_bytes[i])}
            self.trace.counter("telemetry", vals, ts_us=ts)
            for gi in range(tel.num_groups):
                self.trace.counter(
                    f"group{gi}",
                    {"unconverged": int(tel.unconverged[i, gi]),
                     "max_residual": float(tel.max_residual[i, gi])},
                    ts_us=ts)

    def step(self, policy: Optional[SchedulePolicy] = None) -> RunMetrics:
        """A single superstep under `policy`."""
        return self.run(policy, max_supersteps=1)
