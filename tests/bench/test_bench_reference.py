"""The plain references agree with `GraphSession` at a tiny size, and the
controls (the references in the next precision down) do not pass the
configurations' limits.  The controls at the cells' own size run on the
chip: `python3 bench/controls.py --workload <cell> --seeds 1 2 3`."""

import json
import math

import numpy as np
import pytest

import bench_testlib
from bench import reference
from bench.algorithms import ppr, sssp
from bench.graph500 import graph500, search_keys


@pytest.fixture(scope="module")
def g():
    return graph500(9, 16, 0.57, 0.19, 0.19, seed=11)


@pytest.fixture(scope="module")
def g10():
    return graph500(10, 16, 0.57, 0.19, 0.19, seed=11)


def _limits(name):
    return json.loads((bench_testlib.ROOT / "bench" / "configs"
                       / f"{name}.json").read_text())["limits"]


def _session_results(g, jobs, weighted):
    from repro.core import GraphSession, TwoLevel
    from repro.graph.structure import CSRGraph
    w = g.weights if weighted else np.ones_like(g.weights)
    sess = GraphSession(CSRGraph(g.n, g.indptr, g.indices, w), 64,
                        capacity=len(jobs), seed=2)
    hs = [sess.submit(j) for j in jobs]
    m = sess.run(TwoLevel(backend="device", steps_per_sync=math.inf))
    assert m.converged
    return np.stack([sess.result(h) for h in hs])


def test_sssp_reference_agrees_with_session(g):
    roots = search_keys(g, 8, np.random.default_rng(0))
    got = _session_results(g, [sssp.job(r, {}) for r in roots], True)
    nums = sssp.numbers(reference.sssp(g, roots), got)
    assert nums["reach_mismatch"] == 0
    assert nums["dist_rel_gap"] < 1e-6


def test_ppr_reference_agrees_with_session(g):
    cfg = {"damping": 0.85, "tolerance": 1e-7}
    src = search_keys(g, 8, np.random.default_rng(0))
    got = _session_results(g, [ppr.job(s, cfg) for s in src], False)
    ref, low = ppr.reference_results(g, src, cfg)
    assert np.allclose(ref.sum(axis=1), 1.0)
    assert np.all(got <= ref * (1 + 1e-6))       # below, up to rounding
    assert np.sum(ref - got) > 0
    assert ppr.numbers((ref, low), got)["ppr_band_rel"] <= 1e-6


def test_sssp_control_fails_the_limit(g10):
    roots = search_keys(g10, 8, np.random.default_rng(0))
    nums = sssp.numbers(reference.sssp(g10, roots),
                        reference.sssp_bf16(g10, roots))
    assert nums["dist_rel_gap"] > _limits("kron14-sssp")["dist_rel_gap"]


def test_ppr_control_fails_the_limit(g10):
    src = search_keys(g10, 8, np.random.default_rng(0))
    cfg = {"damping": 0.85, "tolerance": 1e-7}
    nums = ppr.numbers(ppr.reference_results(g10, src, cfg),
                       reference.ppr_high(g10, src, 0.85))
    assert nums["ppr_band_rel"] > _limits("kron14-ppr")["ppr_band_rel"]
