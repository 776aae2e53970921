"""Per-superstep telemetry: a fixed-schema time series over one run().

The paper's two claims — CAJS removes redundant data access, MPDS
accelerates convergence — are only honest as CURVES: when in a run the
cache-sharing wins happen, how global-queue occupancy and per-family
residuals evolve, where a live update batch re-ignites work.  A
``TelemetrySeries`` records, per superstep:

  active_jobs       [K]    jobs with pending work this superstep
  tile_loads        [K]    adjacency-block stagings this superstep
  job_block_pushes  [K]    (job, block) processing events this superstep
  tile_pair_loads   [K]    nonzero block-pair stagings this superstep (the
                           CAJS sharing denominator; see RunMetrics)
  halo_bytes        [K]    frontier bytes exchanged across block shards this
                           superstep (0 off the 2D mesh)
  gq_occupancy      [K]    staged-selection occupancy (shared policies:
                           global-queue length <= q; independent: total
                           per-job queue entries)
  dirty_blocks      [K]    update-affected blocks boosted this superstep
                           (nonzero only on the first superstep after
                           apply_updates)
  unconverged       [K, G] unconverged-vertex count per view group
  max_residual      [K, G] max vertex priority per view group (plus-times:
                           max |delta| above tolerance; min-plus: max
                           1/(1+dist) over pending vertices)

Collection is OPT-IN via ``GraphSession(telemetry=...)`` and costs nothing
when off: the host driver skips the bookkeeping and the device driver
compiles the buffers out of the cached superstep entirely (the jit-cache
key carries the telemetry capacity, so on/off sessions never share or
invalidate each other's compilation).

On the device path the series rides the scan carry as preallocated
``[capacity]`` buffers written at index min(superstep, capacity-1), so
``TwoLevel(backend="device", steps_per_sync=inf)`` returns the FULL series
at exactly one host sync.  Runs longer than ``capacity`` supersteps keep
converging correctly; the series is marked ``truncated`` and the overflow
steps collapse into the last row.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np
import jax.numpy as jnp

__all__ = ["TelemetryConfig", "TelemetrySeries", "HostSeriesBuilder",
           "device_buffers", "device_write", "series_from_device",
           "SERIES_FIELDS", "GROUP_FIELDS"]

# the fixed schema: per-superstep scalars ...
SERIES_FIELDS = ("active_jobs", "tile_loads", "job_block_pushes",
                 "gq_occupancy", "dirty_blocks", "tile_pair_loads",
                 "halo_bytes")
# ... and per-(superstep, view-group) columns
GROUP_FIELDS = ("unconverged", "max_residual")

DEFAULT_CAPACITY = 4096


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """What ``GraphSession(telemetry=...)`` turns on.

    capacity      device-path buffer length (finite so the series can ride
                  a while_loop carry; ~30 bytes/superstep)
    trace         record structured trace events on ``session.trace``
                  (submit/detach, superstep spans, apply_updates batches,
                  compactions) for Chrome/Perfetto export

    The program's spans reach a live JAX profiler trace whatever this
    says (``repro.obs.trace.span``).
    """

    capacity: int = DEFAULT_CAPACITY
    trace: bool = True

    @staticmethod
    def coerce(value: Union[None, bool, "TelemetryConfig"]
               ) -> Optional["TelemetryConfig"]:
        """None/False -> disabled; True -> defaults; a config -> itself."""
        if value is None or value is False:
            return None
        if value is True:
            return TelemetryConfig()
        if isinstance(value, TelemetryConfig):
            return value
        raise TypeError(
            f"telemetry must be None, bool or TelemetryConfig: {value!r}")


@dataclasses.dataclass
class TelemetrySeries:
    """One run()'s per-superstep series (numpy, host-side)."""

    view_keys: Tuple[tuple, ...]
    active_jobs: np.ndarray        # [K] int64
    tile_loads: np.ndarray         # [K] int64
    job_block_pushes: np.ndarray   # [K] int64
    gq_occupancy: np.ndarray       # [K] int64
    dirty_blocks: np.ndarray       # [K] int64
    tile_pair_loads: np.ndarray    # [K] int64
    halo_bytes: np.ndarray         # [K] float64
    unconverged: np.ndarray        # [K, G] int64
    max_residual: np.ndarray       # [K, G] float32
    truncated: bool = False        # device buffer overflowed (capacity < K)

    def __len__(self) -> int:
        return int(self.active_jobs.shape[0])

    @property
    def num_groups(self) -> int:
        return int(self.unconverged.shape[1])

    def to_dict(self) -> dict:
        """JSON-ready dict (used by the trace exporter and benchmarks)."""
        d = {"schema": list(SERIES_FIELDS) + list(GROUP_FIELDS),
             "supersteps": len(self),
             "view_keys": [list(map(str, k)) for k in self.view_keys],
             "truncated": self.truncated}
        for f in SERIES_FIELDS:
            d[f] = getattr(self, f).tolist()
        d["halo_bytes"] = [round(float(x), 6) for x in self.halo_bytes]
        d["unconverged"] = self.unconverged.tolist()
        d["max_residual"] = [[round(float(x), 8) for x in row]
                             for row in self.max_residual]
        return d


class HostSeriesBuilder:
    """Per-superstep appender for the host driver (python lists)."""

    def __init__(self, view_keys: Sequence[tuple]):
        self.view_keys = tuple(view_keys)
        self._rows: List[tuple] = []

    def append(self, active_jobs: int, tile_loads: int,
               job_block_pushes: int, gq_occupancy: int, dirty_blocks: int,
               unconverged: Sequence[int],
               max_residual: Sequence[float],
               tile_pair_loads: int = 0, halo_bytes: float = 0.0) -> None:
        self._rows.append((int(active_jobs), int(tile_loads),
                           int(job_block_pushes), int(gq_occupancy),
                           int(dirty_blocks),
                           int(tile_pair_loads), float(halo_bytes),
                           tuple(int(u) for u in unconverged),
                           tuple(float(r) for r in max_residual)))

    def build(self) -> TelemetrySeries:
        g = len(self.view_keys)
        k = len(self._rows)
        cols = list(zip(*self._rows)) if k else [()] * 9
        return TelemetrySeries(
            view_keys=self.view_keys,
            active_jobs=np.asarray(cols[0], dtype=np.int64),
            tile_loads=np.asarray(cols[1], dtype=np.int64),
            job_block_pushes=np.asarray(cols[2], dtype=np.int64),
            gq_occupancy=np.asarray(cols[3], dtype=np.int64),
            dirty_blocks=np.asarray(cols[4], dtype=np.int64),
            tile_pair_loads=np.asarray(cols[5], dtype=np.int64),
            halo_bytes=np.asarray(cols[6], dtype=np.float64),
            unconverged=np.asarray(cols[7], dtype=np.int64).reshape(k, g),
            max_residual=np.asarray(cols[8], dtype=np.float32).reshape(k, g))


# ---------------------------------------------------------------------------
# device-path buffers (ride the scan/while_loop carry)
# ---------------------------------------------------------------------------


def device_buffers(capacity: int, n_groups: int):
    """Preallocated [capacity] buffers for the jitted superstep carry."""
    z = jnp.zeros
    return (z(capacity, jnp.int32),               # active_jobs
            z(capacity, jnp.int32),               # tile_loads
            z(capacity, jnp.int32),               # job_block_pushes
            z(capacity, jnp.int32),               # gq_occupancy
            z(capacity, jnp.int32),               # dirty_blocks
            z(capacity, jnp.int32),               # tile_pair_loads
            z(capacity, jnp.float32),             # halo_bytes
            z((capacity, n_groups), jnp.int32),   # unconverged
            z((capacity, n_groups), jnp.float32))  # max_residual


def device_write(bufs, idx, active_jobs, tile_loads, job_block_pushes,
                 gq_occupancy, dirty_blocks, unconverged, max_residual,
                 tile_pair_loads=0, halo_bytes=0.0):
    """Write superstep `idx`'s row (traced; idx pre-clamped by the caller).

    Overflow rows alias the LAST slot (`.set` keeps the newest write), so
    a truncated series still ends at the run's final state.
    """
    a, t, p, o, d, pl, h, u, r = bufs
    scalars = (active_jobs, tile_loads, job_block_pushes, gq_occupancy,
               dirty_blocks, tile_pair_loads)
    a, t, p, o, d, pl = (b.at[idx].set(jnp.asarray(v, jnp.int32))
                         for b, v in zip((a, t, p, o, d, pl), scalars))
    h = h.at[idx].set(jnp.asarray(halo_bytes, jnp.float32))
    u = u.at[idx].set(jnp.asarray(unconverged, jnp.int32))
    r = r.at[idx].set(jnp.asarray(max_residual, jnp.float32))
    return (a, t, p, o, d, pl, h, u, r)


def series_from_device(bufs, supersteps: int,
                       view_keys: Sequence[tuple]) -> TelemetrySeries:
    """Slice the carried buffers down to the executed supersteps."""
    cap = int(bufs[0].shape[0])
    k = min(int(supersteps), cap)
    a, t, p, o, d, pl, h, u, r = (np.asarray(b)[:k] for b in bufs)
    return TelemetrySeries(
        view_keys=tuple(view_keys),
        active_jobs=a.astype(np.int64), tile_loads=t.astype(np.int64),
        job_block_pushes=p.astype(np.int64),
        gq_occupancy=o.astype(np.int64), dirty_blocks=d.astype(np.int64),
        tile_pair_loads=pl.astype(np.int64), halo_bytes=h.astype(np.float64),
        unconverged=u.astype(np.int64), max_residual=r.astype(np.float32),
        truncated=int(supersteps) > cap)
