"""A job's admission and retirement as one compiled program each.

  * after `submit`, the slot holds exactly `alg.init(g)` and
    `alg.get_push_scale()`; after `detach`, the result is exactly the
    eager `alg.result(...)[:n_real]` and the slot holds the inert fill;
  * one admit and one retire program serve every slot and source of a
    view (`slot_compiles` counts each compilation), and a capacity
    doubling compiles each once more;
  * heterogeneous sessions and stale-handle generations behave as before;
  * on a placed session the state stays sharded over "jobs".
"""

import contextlib
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.algorithms import (BFS, Katz, PageRank, PersonalizedPageRank,
                              SSSP, WCC)
from repro.core import Fused, GraphSession, TwoLevel
from repro.graph import rmat_graph
from repro.obs import trace

CSR = rmat_graph(300, 5, seed=7)


@contextlib.contextmanager
def profiled(path):
    """A live profiler trace (the digest records only then), over an
    empty digest."""
    trace.reset_digest()
    jax.profiler.start_trace(str(path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _slot_programs(sess):
    return {k[0]: fn for k, fn in sess._jit_cache.items()
            if k[0] in ("admit", "retire")}


def _row(x, slot):
    return np.asarray(jax.device_get(x))[slot]


@pytest.mark.parametrize("alg", [
    SSSP(source=17), BFS(source=250), WCC(), PageRank(damping=0.8),
    PersonalizedPageRank(source=123), Katz(alpha=0.02)],
    ids=lambda a: type(a).__name__)
def test_slot_programs_match_the_eager_ops(alg):
    sess = GraphSession(CSR, 32, capacity=4, seed=3)
    other = sess.submit(type(alg)())          # slot 0: another job
    h = sess.submit(alg)
    grp = sess.view_groups()[0]
    v, d = alg.init(grp.graph)
    np.testing.assert_array_equal(_row(grp.values, h.slot), np.asarray(v))
    np.testing.assert_array_equal(_row(grp.deltas, h.slot), np.asarray(d))
    assert _row(grp.push_scale, h.slot) == np.float32(alg.get_push_scale())
    sess.run(Fused(steps_per_sync=4), 12)
    want = np.asarray(alg.result(grp.values[h.slot], grp.deltas[h.slot]))
    kept = [_row(x, other.slot) for x in (grp.values, grp.deltas)]
    got = sess.detach(h)
    np.testing.assert_array_equal(got, want.reshape(-1)[:CSR.n])
    fill = 0.0 if alg.semiring == "plus_times" else np.inf
    assert (_row(grp.values, h.slot) == fill).all()
    assert (_row(grp.deltas, h.slot) == fill).all()
    assert _row(grp.push_scale, h.slot) == 1.0
    for x, k in zip((grp.values, grp.deltas), kept):
        np.testing.assert_array_equal(_row(x, other.slot), k)


def test_one_compilation_serves_every_slot_and_source(tmp_path):
    sess = GraphSession(CSR, 32, capacity=64, seed=3)
    rng = np.random.default_rng(0)
    sources = rng.permutation(CSR.n)[:64]
    with profiled(tmp_path):
        hs = [sess.submit(SSSP(source=int(s))) for s in sources[:32]]
        # numpy sources share the compilation of Python ones
        hs += [sess.submit(SSSP(source=s)) for s in sources[32:]]
        for i in rng.permutation(64):
            sess.detach(hs[i])
        counters = trace.digest()["counters"]
    progs = _slot_programs(sess)
    assert sorted(progs) == ["admit", "retire"]
    assert {k: p._cache_size() for k, p in progs.items()} == {
        "admit": 1, "retire": 1}
    assert counters["slot_programs"] == 128
    assert counters["slot_compiles"] == 2
    with profiled(tmp_path):
        hs = [sess.submit(SSSP(source=s)) for s in range(65)]  # doubles
        for h in hs:
            sess.detach(h)
        counters = trace.digest()["counters"]
    assert sess.capacity == 128
    assert {k: p._cache_size() for k, p in _slot_programs(sess).items()} \
        == {"admit": 2, "retire": 2}
    assert counters["slot_programs"] == 130
    assert counters["slot_compiles"] == 2


def test_heterogeneous_session_and_stale_handles():
    algs = [SSSP(source=0), PageRank(), PersonalizedPageRank(source=11),
            WCC(), SSSP(source=42)]
    sess = GraphSession(CSR, 32, capacity=2, seed=5)
    hs = [sess.submit(a) for a in algs]
    assert len(sess.view_groups()) == 3
    sess.run(TwoLevel(), 4)
    gone = sess.detach(hs[0])
    assert gone.shape == (CSR.n,)
    with pytest.raises(KeyError):
        sess.detach(hs[0])
    with pytest.raises(KeyError):
        sess.result(hs[0])
    again = sess.submit(SSSP(source=7))
    assert (again.slot, again.gen) == (hs[0].slot, hs[0].gen + 1)
    with pytest.raises(KeyError):
        sess.result(hs[0])
    assert sess.run(TwoLevel(), 20000).converged
    solo = GraphSession(CSR, 32, capacity=1, seed=5)
    for h, a in zip([again] + hs[1:], [SSSP(source=7)] + algs[1:]):
        ref = solo.submit(a)
        assert solo.run(TwoLevel(), 20000).converged
        want = solo.detach(ref)
        if a.semiring == "min_plus":
            np.testing.assert_array_equal(sess.detach(h), want)
        else:
            np.testing.assert_allclose(sess.detach(h), want, rtol=1e-3,
                                       atol=1e-5)
    assert sess.num_active == 0


PLACED_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.algorithms import PageRank, PersonalizedPageRank, SSSP
from repro.core import Fused, GraphSession
from repro.dist.graph import make_job_mesh
from repro.dist.mesh2d import make_mesh2d
from repro.graph import rmat_graph

mesh = make_job_mesh(4) if sys.argv[1] == "jobs" else make_mesh2d(2, 2)
sess = GraphSession(rmat_graph(200, 5, seed=13), 16, capacity=4, seed=5)
hs = [sess.submit(SSSP(source=s)) for s in (0, 42)] + [
    sess.submit(PageRank())]
sess.run(Fused(), 4, mesh=mesh)
placed = {g.key: [x.sharding for x in (g.values, g.deltas, g.push_scale)]
          for g in sess.view_groups()}
hs.append(sess.submit(SSSP(source=7)))
sess.detach(hs[0])
hs.append(sess.submit(PersonalizedPageRank(source=3)))
sess.detach(hs[2])
for g in sess.view_groups():
    now = [x.sharding for x in (g.values, g.deltas, g.push_scale)]
    assert now == placed[g.key], (now, placed[g.key])
    assert g.values.sharding.spec[0] == "jobs", g.values.sharding
assert sess.run(Fused(), 20000, mesh=mesh).converged
print("PLACED-OK")
"""


@pytest.mark.parametrize("mesh", ["jobs", "jobs_blocks"])
def test_placed_session_keeps_job_sharding(mesh):
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    pythonpath = src + os.pathsep + os.environ.get("PYTHONPATH", "")
    result = subprocess.run(
        [sys.executable, "-c", PLACED_SCRIPT, mesh],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": pythonpath.rstrip(os.pathsep)})
    assert "PLACED-OK" in result.stdout, result.stderr[-2000:]
