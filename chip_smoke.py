#!/usr/bin/env python3
"""Chip smoke test: concurrent graph jobs on a TPU, end to end.

Drives the system's main path once through the entry points a user calls
(`GraphSession.submit` / `run` / `result`, `OpenLoopHarness`) at the size
one 16 GB TPU v5e chip must hold: a scale-15 R-MAT graph (32,768
vertices, edge factor 16, Graph500 a/b/c) blocked at Vb=64, whose
adjacency view is ~5.4 GB of f32 tiles.

  python chip_smoke.py             phases A, B, C on one chip
  python chip_smoke.py --chips 4   phase A on a 2x2 (jobs x blocks) mesh,
                                   compared with the same session on one
                                   of the chips; no other phase

  A  64 SSSP jobs from seeded roots under the paper's two-level policy on
     the device driver (TwoLevel(backend="device", steps_per_sync=inf)),
     once through the jnp push and once through the fused Pallas kernel;
     both are compared bitwise with a float32 Bellman-Ford in numpy.
  B  PageRank + 63 personalized PageRank jobs on the unweighted graph
     through the Pallas kernel, compared with a float64 power iteration
     of the same definition.
  C  an open-loop serve run (PageRank/PPR tenants only, so the phase-B
     view is reused) in which every admitted request must complete.

Earlier lines report graph and view sizes, device memory, supersteps,
host syncs, compile and run seconds (smoke timings, not benchmark
numbers), the largest error against each reference, and whether the
compiled superstep holds the Pallas kernel (`tpu_custom_call`).  The
last line is one JSON object naming the device.  The script exits
non-zero, without that line, off TPU or when any check fails.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SCALE = 15                          # R-MAT scale: 2**15 vertices
EDGE_FACTOR = 16
SEED = 0
N_JOBS = 64
BLOCK = 64
MAX_SUPERSTEPS = 100_000
MESH_MEMORY_FRACTION = 0.8          # a 2x2 mesh chip holds at most this
                                    # share of the one-chip leg's bytes
PR_RTOL, PR_ATOL = 1e-3, 1e-5       # tests/test_session.py's PageRank bar


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def require_tpu():
    import jax
    dev = jax.devices()[0]
    require(dev.platform == "tpu",
            f"needs a TPU, JAX found {dev.platform} ({dev.device_kind})")
    return dev


def gb(nbytes: float) -> str:
    return f"{nbytes / 1e9:.3f} GB"


def memory(tag: str, devices) -> list:
    stats = [d.memory_stats() or {} for d in devices]
    for d, s in zip(devices, stats):
        log(f"[{tag}] device {d.id}: bytes_in_use "
            f"{gb(s.get('bytes_in_use', 0))}, peak_bytes_in_use "
            f"{gb(s.get('peak_bytes_in_use', 0))}")
    return stats


def live_arrays(tag: str, devices) -> None:
    """Itemise each device's memory: the bytes of every live JAX array's
    shard there (each device buffer once: a sharded array's per-device
    views share its buffers), summed, and the four largest by shape."""
    import jax
    per = {d.id: {} for d in devices}
    seen = set()
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            buf = (s.device.id, s.data.unsafe_buffer_pointer())
            if s.device.id not in per or buf in seen:
                continue
            seen.add(buf)
            key = (tuple(a.shape), tuple(s.data.shape), str(a.dtype))
            per[s.device.id][key] = (per[s.device.id].get(key, 0)
                                     + s.data.nbytes)
    for d in devices:
        items = sorted(per[d.id].items(), key=lambda kv: -kv[1])
        log(f"[{tag}] device {d.id}: live arrays "
            f"{gb(sum(per[d.id].values()))}; largest " + "; ".join(
                f"{shape} as {shard} {dtype} {gb(n)}"
                for (shape, shard, dtype), n in items[:4]))


# ---------------------------------------------------------------------------
# references: plain numpy over the CSR, independent of the engine
# ---------------------------------------------------------------------------


def _edges_by_dst(csr):
    src = np.repeat(np.arange(csr.n), np.diff(csr.indptr))
    order = np.argsort(csr.indices, kind="stable")
    return src[order], csr.indices[order], csr.weights[order]


def bellman_ford(csr, roots) -> np.ndarray:
    """[len(roots), n] float32 shortest distances, Jacobi Bellman-Ford:
    d(v) = min(d(v), min_u fl32(d(u) + w(u, v))) until nothing changes."""
    src, dst, w = _edges_by_dst(csr)
    starts = np.flatnonzero(np.r_[True, dst[1:] != dst[:-1]])
    heads = dst[starts]
    dist = np.full((len(roots), csr.n), np.inf, np.float32)
    dist[np.arange(len(roots)), roots] = 0.0
    while True:
        best = np.minimum.reduceat(dist[:, src] + w, starts, axis=1)
        new = dist.copy()
        new[:, heads] = np.minimum(dist[:, heads], best)
        if np.array_equal(new, dist):
            return dist
        dist = new


def power_iteration(csr, sources, damping=0.85) -> np.ndarray:
    """Row 0: PageRank x = (1-d) 1 + d W^T x; row i > 0: personalized
    PageRank from sources[i-1], x = (1-d) e_s + d W^T x; W = out-degree
    normalized adjacency (the engine's delta-accumulative definition)."""
    import scipy.sparse as sp
    src = np.repeat(np.arange(csr.n), np.diff(csr.indptr))
    deg = np.maximum(np.diff(csr.indptr), 1).astype(np.float64)
    a = sp.csr_matrix((1.0 / deg[src], (src, csr.indices)),
                      shape=(csr.n, csr.n))
    b = np.zeros((1 + len(sources), csr.n))
    b[0] = 1.0 - damping
    b[np.arange(1, 1 + len(sources)), sources] = 1.0 - damping
    x = b.copy()
    for _ in range(10_000):
        nxt = b + damping * np.asarray((a.T @ x.T).T)
        if np.max(np.abs(nxt - x)) < 1e-13:
            return nxt
        x = nxt
    raise RuntimeError("power iteration did not converge")


# ---------------------------------------------------------------------------
# one leg: AOT-compile the session's device superstep, then run it
# ---------------------------------------------------------------------------


def run_leg(sess, handles, policy, label: str, kernel: bool) -> np.ndarray:
    import jax
    from repro.core.policy import device_step_args
    from repro.kernels.common import default_interpret
    step = sess._device_step_fn(policy)
    args = device_step_args(sess, MAX_SUPERSTEPS)
    if kernel:
        require(sess.use_pallas and not default_interpret(),
                f"{label}: Pallas kernels would run interpreted")
        require(all(p is not None for p in args[5]),
                f"{label}: no pair view, the push would fall back to ELL")
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    log(f"[{label}] compile {compile_s:.2f} s (AOT lower+compile), "
        f"tpu_custom_call in superstep HLO: {has_kernel}")
    if kernel:
        require(has_kernel, f"{label}: compiled superstep has no kernel")
    t0 = time.perf_counter()
    m1 = sess.run(policy, max_supersteps=1)
    jax.block_until_ready([g.values for g in sess.view_groups()])
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    m = sess.run(policy, MAX_SUPERSTEPS)
    jax.block_until_ready([g.values for g in sess.view_groups()])
    run_s = time.perf_counter() - t0
    require(m.converged, f"{label}: did not converge")
    log(f"[{label}] smoke timing, not a benchmark: first call (1 "
        f"superstep, program load) {first_s:.2f} s, run to fixpoint "
        f"{run_s:.2f} s; supersteps {m1.supersteps + m.supersteps}, "
        f"host_syncs {m1.host_syncs + m.host_syncs}, tile_loads "
        f"{m1.tile_loads + m.tile_loads}")
    return np.stack([np.asarray(sess.result(h)) for h in handles])


def describe_view(sess, tag: str) -> None:
    for g in sess.view_groups():
        pairs = sess._pair_data(g)
        log(f"[{tag}] view {g.key[:2]}: B_N={g.graph.num_blocks} "
            f"Vb={g.graph.block_size} K_max={g.graph.max_nbr_blocks} "
            f"P={pairs.num_pairs}; ELL tiles {gb(g.graph.tiles.nbytes)}, "
            f"pair tiles {gb(pairs.tiles.nbytes)} (f32, unpadded); "
            f"job capacity {g.capacity}")


def max_abs_error(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest |got - ref| where ref is finite (unreached SSSP vertices
    are +inf on both sides when the results agree)."""
    fin = np.isfinite(ref)
    return float(np.max(np.abs(got[fin] - ref[fin]))) if fin.any() else 0.0


def bitwise_equal(a: np.ndarray, b: np.ndarray) -> bool:
    return a.shape == b.shape and np.array_equal(
        a.astype(np.float32).view(np.uint32),
        b.astype(np.float32).view(np.uint32))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_a(csr_w, roots, dev):
    from repro.algorithms import SSSP
    from repro.core import GraphSession, TwoLevel
    policy = TwoLevel(backend="device", steps_per_sync=math.inf)
    sess = GraphSession(csr_w, BLOCK, capacity=N_JOBS, seed=SEED)
    handles = [sess.submit(SSSP(source=int(r))) for r in roots]
    describe_view(sess, "A")
    got_jnp = run_leg(sess, handles, policy, "A jnp", kernel=False)
    for h in handles:
        sess.detach(h)
    sess.use_pallas = True
    handles = [sess.submit(SSSP(source=int(r))) for r in roots]
    got_pallas = run_leg(sess, handles, policy, "A pallas", kernel=True)
    stats = memory("A", [dev])[0]
    require(stats.get("peak_bytes_in_use", 0) >= 5e9,
            "phase A held under 5 GB on the device")
    del sess, handles
    gc.collect()
    t0 = time.perf_counter()
    ref = bellman_ford(csr_w, roots)
    log(f"[A] numpy Bellman-Ford {time.perf_counter() - t0:.1f} s; "
        f"reachable {int(np.isfinite(ref).sum())} of {ref.size}")
    for label, got in (("jnp", got_jnp), ("pallas", got_pallas)):
        eq = bitwise_equal(got, ref)
        log(f"[A] {label} vs Bellman-Ford: max abs error "
            f"{max_abs_error(got, ref)}, bitwise equal {eq}")
        require(eq, f"phase A {label} differs from Bellman-Ford")
    return got_pallas


def phase_b(csr, sources, dev):
    from repro.algorithms import PageRank, PersonalizedPageRank
    from repro.core import GraphSession, TwoLevel
    policy = TwoLevel(backend="device", steps_per_sync=math.inf)
    sess = GraphSession(csr, BLOCK, capacity=N_JOBS, seed=SEED,
                        use_pallas=True)
    handles = [sess.submit(PageRank())] + [
        sess.submit(PersonalizedPageRank(source=int(s))) for s in sources]
    describe_view(sess, "B")
    got = run_leg(sess, handles, policy, "B pallas", kernel=True)
    memory("B", [dev])
    t0 = time.perf_counter()
    ref = power_iteration(csr, sources)
    err = np.abs(got - ref)
    log(f"[B] numpy power iteration {time.perf_counter() - t0:.1f} s; "
        f"max abs error {float(err.max())}, max error / (atol + rtol*|ref|) "
        f"{float(np.max(err / (PR_ATOL + PR_RTOL * np.abs(ref))))}")
    require(np.allclose(got, ref, rtol=PR_RTOL, atol=PR_ATOL),
            "phase B differs from the power iteration")
    for h in handles:
        sess.detach(h)
    return sess


def phase_c(sess, dev):
    from repro.core import Fused
    from repro.obs import LoadgenConfig, OpenLoopHarness
    from repro.serve.concurrent import ConcurrentServeScheduler
    cfg = LoadgenConfig(seed=SEED, ticks=40, base_rate=0.75,
                        n_tenants=24,
                        families=(("pagerank", 0.3), ("ppr", 0.7)))
    sched = ConcurrentServeScheduler(sess.scheduler.num_blocks,
                                     batch_budget=8, seed=SEED)
    harness = OpenLoopHarness(sess, sched, cfg, policy=Fused(),
                              max_running=8, supersteps_per_tick=8)
    t0 = time.perf_counter()
    s = harness.run()
    wall = time.perf_counter() - t0
    lat = s["latency_ticks"]
    log(f"[C] {s['arrivals']} arrivals, {s['admitted']} admitted, "
        f"{s['completed']} completed in {s['ticks']} ticks; supersteps "
        f"{s['supersteps']}, host_syncs {s['counters']['host_syncs']}; "
        f"latency ticks p50 {lat['p50']} p99 {lat['p99']}; smoke wall "
        f"{wall:.2f} s")
    memory("C", [dev])
    require(s["arrivals"] > 0 and s["admitted"] == s["arrivals"],
            "phase C left arrivals unadmitted")
    require(s["completed"] == s["admitted"],
            "phase C left admitted requests incomplete")


def phase_a_mesh(csr_w, roots, devices):
    """Phase A's Pallas leg on a 2x2 (jobs x blocks) mesh vs one chip."""
    import jax
    from repro.algorithms import SSSP
    from repro.core import GraphSession, TwoLevel
    from repro.dist.mesh2d import make_mesh2d
    policy = TwoLevel(backend="device", steps_per_sync=math.inf)
    sess = GraphSession(csr_w, BLOCK, capacity=N_JOBS, seed=SEED,
                        use_pallas=True)
    handles = [sess.submit(SSSP(source=int(r))) for r in roots]
    describe_view(sess, "A4")
    g = sess.view_groups()[0]
    bn, num_pairs = g.graph.num_blocks, sess._pair_data(g).num_pairs
    ref = run_leg(sess, handles, policy, "A4 one chip", kernel=True)
    one_chip = memory("A4 one chip", devices[:1])[0].get("bytes_in_use", 0)
    del sess, handles, g
    gc.collect()
    sess = GraphSession(csr_w, BLOCK, capacity=N_JOBS, seed=SEED,
                        use_pallas=True)
    handles = [sess.submit(SSSP(source=int(r))) for r in roots]
    mesh = make_mesh2d(jobs=2, blocks=2)
    t0 = time.perf_counter()
    m1 = sess.run(policy, max_supersteps=1, mesh=mesh)
    jax.block_until_ready([g.values for g in sess.view_groups()])
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    m = sess.run(policy, MAX_SUPERSTEPS)
    jax.block_until_ready([g.values for g in sess.view_groups()])
    run_s = time.perf_counter() - t0
    require(m.converged, "2x2 mesh run did not converge")
    log(f"[A4 mesh 2x2] smoke timing, not a benchmark: first call "
        f"(placement, compile, 1 superstep) {first_s:.2f} s, run to "
        f"fixpoint {run_s:.2f} s; supersteps {m1.supersteps + m.supersteps}"
        f", host_syncs {m1.host_syncs + m.host_syncs}, halo_bytes "
        f"{m1.halo_bytes + m.halo_bytes}")
    g = sess.view_groups()[0]
    ell = {d.id: g.graph.tiles.sharding.shard_shape(g.graph.tiles.shape)
           for d in g.graph.tiles.sharding.device_set}
    pt = g.pair_shards[2].tiles
    pairs = {d.id: pt.sharding.shard_shape(pt.shape)
             for d in pt.sharding.device_set}
    for d in devices:
        log(f"[A4 mesh 2x2] device {d.id}: ELL-tile shard "
            f"{tuple(ell[d.id])} of B_N={bn}, pair-tile shard "
            f"{tuple(pairs[d.id])} of P={num_pairs}")
        require(ell[d.id][0] == bn // 2,
                f"device {d.id} holds {ell[d.id][0]} of {bn} ELL blocks")
        require(pairs[d.id][1] < num_pairs,
                f"device {d.id} holds all {num_pairs} pair tiles")
    live_arrays("A4 mesh 2x2", devices)
    stats = memory("A4 mesh 2x2", devices)
    for d, st in zip(devices, stats):
        used = st.get("bytes_in_use", 0)
        require(used <= MESH_MEMORY_FRACTION * one_chip,
                f"device {d.id} holds {gb(used)}, not clearly below the "
                f"one-chip leg's {gb(one_chip)}")
    got = np.stack([np.asarray(sess.result(h)) for h in handles])
    eq = bitwise_equal(got, ref)
    log(f"[A4] 2x2 mesh vs one chip: bitwise equal {eq}, max abs error "
        f"{max_abs_error(got, ref)}")
    require(eq, "2x2 mesh results differ from one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    dev = require_tpu()
    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.graph import rmat_graph
    devices = jax.devices()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {enable_compile_cache()}")
    require(len(devices) >= args.chips,
            f"--chips {args.chips} but JAX sees {len(devices)}")
    n = 2 ** SCALE
    t0 = time.perf_counter()
    csr_w = rmat_graph(n, EDGE_FACTOR, seed=SEED, weighted=True)
    roots = np.random.default_rng(SEED).choice(n, N_JOBS, replace=False)
    log(f"graph: R-MAT scale {SCALE}, n={csr_w.n}, nnz={csr_w.nnz}, "
        f"{N_JOBS} jobs, generated in {time.perf_counter() - t0:.1f} s")
    if args.chips == 4:
        phase_a_mesh(csr_w, roots, devices[:4])
    else:
        phase_a(csr_w, roots, dev)
        # phase A's ~5.4 GB view must be gone before phase B builds its own
        jax.clear_caches()
        gc.collect()
        memory("A freed", [dev])
        csr = rmat_graph(n, EDGE_FACTOR, seed=SEED)
        sess = phase_b(csr, roots[:N_JOBS - 1], dev)
        phase_c(sess, dev)
    dry = [m for m in sys.modules
           if m.startswith("repro.launch.") and ("dryrun" in m
                                                 or "hillclimb" in m)]
    require(not dry, f"imported CPU-forcing modules {dry}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
