"""The per-layer metrics that read the program's span digest.

A tiny session on the CPU runs a batch of jobs and the serve front's
admission under a live profiler trace, as a `--trace 1` window does; each
reader then reads that digest through a hand-built `harness.Run`, and
reads nothing where the digest is empty or the program keeps none."""

import contextlib
import math

import jax
import numpy as np
import pytest

import bench_testlib
from bench import graph500, harness
from repro.obs import trace

ROOT = bench_testlib.ROOT
CELL = "sssp-batch8"
SERVE_CELL = "ppr-serve-open"
READERS = ["submit_ms_per_job.batch", "submit_ms_per_job.serve",
           "detach_ms_per_job.batch", "detach_ms_per_job.serve",
           "device_reads_per_query.batch", "device_reads_per_query.serve",
           "schedule_ms_per_step.serve", "streamed_pair_share"]


@contextlib.contextmanager
def profiled(path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    trace.reset_digest()
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(run, digest, metrics): one batch of 4 SSSP jobs through submit,
    run, poll and detach, and three serve admissions, traced."""
    from repro.core import Fused
    from repro.serve.concurrent import (ConcurrentServeScheduler, Request,
                                        RequestStream)
    cell = harness.load_cell(CELL)
    config = dict(cell.config, scale=9,
                  engine=dict(cell.config["engine"], use_pallas=False))
    rng = np.random.default_rng(3)
    g = harness.make_graph(config, rng)
    sess = harness.make_session(g, config, 4, rng)
    alg = harness.load_module(ROOT, "algorithms", "sssp")
    keys = g.keys[:4]
    sched = ConcurrentServeScheduler(8, 2, seed=0)
    stream = RequestStream(0, "ppr")
    sched.add_stream(stream)
    for k in range(5):
        stream.add(Request(0, k, 1.0, 1))
    rec = harness.Recorder()
    rec.counting = True
    queries = []
    with profiled(tmp_path_factory.mktemp("prof")):
        hs = [sess.submit(alg.job(r, config)) for r in keys]
        m = sess.run(Fused(), 100_000)
        rec.add(m)
        sess.unconverged_counts()
        for r, h in zip(keys, hs):
            queries.append(harness.Query(source=int(r), due_s=0.0,
                                         done_s=1.0,
                                         result=sess.detach(h)))
        for _ in range(3):
            sched.schedule_step()
    block = int(config["engine"]["block_size"])
    run = harness.Run(
        cell=cell, setup_s=1.0, window_s=1.0, queries=queries,
        recorder=rec,
        shapes={"jobs": 4, "num_blocks": -(-g.n // block), "block": block,
                "num_pairs": graph500.block_pairs(g, block),
                "semiring": alg.SEMIRING, "q": sess.q,
                "ell_slots": sess.view_groups()[0].graph.tiles.shape[1]},
        peaks=harness.load_peaks(ROOT, "TPU v5 lite"), trace=None)
    return run, trace.digest(), m


def test_readers_are_declared_for_their_cells():
    cells = {m["name"]: m for m in harness.load_cell(CELL).per_layer}
    serve = {m["name"]: m for m in harness.load_cell(SERVE_CELL).per_layer}
    for name in READERS:
        assert name in cells or name in serve
        assert harness.load_metric(ROOT, name).read
    assert "selected_pair_share" in cells and "streamed_pair_share" in cells


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_digest(recorded, name):
    run, d, m = recorded
    spans, counters = d["spans"], d["counters"]
    got = harness.load_metric(ROOT, name).read(run)
    base = name.split(".")[0]
    if base == "submit_ms_per_job":
        s = spans["session.submit"]
        want = 1e3 * s["total_s"] / s["count"]
        assert s["count"] == 4
    elif base == "detach_ms_per_job":
        s = spans["session.detach"]
        want = 1e3 * s["total_s"] / s["count"]
        assert s["count"] == 4
    elif base == "device_reads_per_query":
        # a chunk, the readout, the poll and one result a job
        assert counters["device_reads"] == m.host_syncs + 1 + 1 + 4
        want = counters["device_reads"] / 4
    elif base == "schedule_ms_per_step":
        s = spans["serve.schedule"]
        assert s["count"] == 3
        want = 1e3 * s["self_s"] / 3
    else:
        assert counters["pairs_streamed"] == m.pairs_streamed
        want = (100.0 * m.pairs_streamed
                / (run.shapes["num_pairs"] * m.supersteps))
        # the jnp min-plus push reads the K ELL tiles of each of the q
        # selection slots once a superstep
        q, k = run.shapes["q"], run.shapes["ell_slots"]
        assert want == pytest.approx(100.0 * q * k / run.shapes["num_pairs"])
    assert got is not None and math.isfinite(got) and got > 0
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_a_digest(recorded, name,
                                               monkeypatch):
    run, d, _ = recorded
    reader = harness.load_metric(ROOT, name)
    saved = (dict(trace._spans), dict(trace._counters))
    try:
        trace.reset_digest()
        assert reader.read(run) is None
        # a program that keeps no digest at all
        monkeypatch.delattr(trace, "digest")
        assert reader.read(run) is None
    finally:
        trace._spans.update(saved[0])
        trace._counters.update(saved[1])
