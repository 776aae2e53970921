"""repro.obs.trace: the program's spans and counters on the profiler's
clock, and the digest of the profiled interval.

  * with no profiler live, spans and counters record nothing in the
    digest, and the recorder's events are what they are with one live;
  * under `jax.profiler.start_trace`, a session's submit / run / poll /
    detach and the serve front's schedule_step give digest counts equal
    to the calls, self time <= total, one `device_reads` per blocking
    read, one `slot_programs` per submit or detach and no
    `slot_compiles` once the programs exist;
  * the `repro.*` annotations sit on the xplane's host plane, each child
    inside its parent and all inside an enclosing annotation;
  * `pairs_streamed` is the fused kernel's grid pair steps per superstep,
    on both drivers.
"""

import contextlib
import glob
import os
import sys
import threading

import jax
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.algorithms import PersonalizedPageRank, SSSP
from repro.core import Fused, GraphSession, TwoLevel
from repro.graph import rmat_graph
from repro.kernels import common
from repro.kernels.fused_superstep import kernel as fused_kernel
from repro.kernels.fused_superstep.ops import block_vmem_bytes
from repro.obs import trace
from repro.serve.concurrent import (ConcurrentServeScheduler, Request,
                                    RequestStream)

CSR = rmat_graph(300, 5, seed=7)


@contextlib.contextmanager
def profiled(path):
    """A live profiler trace with the benchmark's options, over an empty
    digest."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    trace.reset_digest()
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _job_story(sess):
    """Three SSSP jobs through submit, a chunked device run, a poll and
    detach; returns the run's metrics."""
    hs = [sess.submit(SSSP(source=s)) for s in (0, 5, 9)]
    m = sess.run(Fused(steps_per_sync=4), 1000)
    sess.unconverged_counts()
    for h in hs:
        sess.detach(h)
    return m


def test_no_profiler_no_digest_and_the_recorder_unchanged(tmp_path):
    assert not TraceAnnotation.is_enabled()
    trace.reset_digest()
    off = GraphSession(CSR, 32, capacity=4, seed=3)
    _job_story(off)
    trace.count("device_reads", 1)
    assert trace.digest() == {"spans": {}, "counters": {}}
    assert off.trace.events == []

    def story():
        sess = GraphSession(CSR, 32, capacity=4, seed=3, telemetry=True)
        _job_story(sess)
        return [(e["name"], e["ph"], sorted(e["args"]))
                for e in sess.trace.events]

    quiet = story()
    assert trace.digest() == {"spans": {}, "counters": {}}
    with profiled(tmp_path):
        live = story()
    assert quiet == live
    names = [n for n, _, _ in quiet]
    assert names.count("session.submit") == 3
    assert names.count("session.detach") == 3
    assert "session.run" in names and "session.run.chunk" in names
    # the program's inner spans stay off the recorder
    assert "session.submit.program" not in names


def test_digest_counts_the_calls(tmp_path):
    sess = GraphSession(CSR, 32, capacity=4, seed=3)
    _job_story(sess)                       # compile outside the trace
    sched = ConcurrentServeScheduler(4, 2, seed=0)
    st = RequestStream(0)
    sched.add_stream(st)
    for g in range(3):
        st.add(Request(0, g, 1.0, 1))
    with profiled(tmp_path):
        m = _job_story(sess)
        sched.schedule_step()
        sched.schedule_step()
    d = trace.digest()
    spans = d["spans"]
    calls = {"session.submit": 3, "session.submit.program": 3,
             "session.detach": 3, "session.detach.program": 3,
             "session.detach.read": 3,
             "session.poll": 1, "session.run": 1,
             "session.run.chunk": m.host_syncs,
             "session.run.chunk.wait": m.host_syncs,
             "session.run.readout": 1, "serve.schedule": 2}
    assert {k: v["count"] for k, v in spans.items()} == calls
    for s in spans.values():
        assert 0 <= s["self_s"] <= s["total_s"]
        assert 0 < s["max_s"] <= s["total_s"]
    sub = spans["session.submit"]
    kids = spans["session.submit.program"]["total_s"]
    assert sub["self_s"] == pytest.approx(sub["total_s"] - kids, abs=1e-9)
    det = spans["session.detach"]
    kids = (spans["session.detach.program"]["total_s"]
            + spans["session.detach.read"]["total_s"])
    assert det["self_s"] == pytest.approx(det["total_s"] - kids, abs=1e-9)
    # blocking reads: one per chunk, the readout, the poll (one view) and
    # one per detached result
    assert d["counters"]["device_reads"] == m.host_syncs + 1 + 1 + 3
    assert d["counters"]["pairs_streamed"] == m.pairs_streamed > 0
    # one admit or retire program a job's submit and detach; all compiled
    # by the first story, outside the trace
    assert d["counters"]["slot_programs"] == 3 + 3
    assert d["counters"]["slot_compiles"] == 0
    trace.reset_digest()
    assert trace.digest() == {"spans": {}, "counters": {}}


def _host_events(path):
    xp = sorted(glob.glob(os.path.join(str(path), "plugins", "profile",
                                       "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(xp).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("repro.", "bench.")):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return out


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_spans_nest_on_the_xplane_host_plane(tmp_path):
    sess = GraphSession(CSR, 32, capacity=4, seed=3)
    _job_story(sess)
    with profiled(tmp_path):
        with TraceAnnotation("bench.outer"):
            _job_story(sess)
    evs = _host_events(tmp_path)
    by = {}
    for ev in evs:
        by.setdefault(ev[0], []).append(ev)
    assert len(by["repro.session.submit"]) == 3
    assert len(by["repro.session.detach"]) == 3
    nest = {"repro.session.submit.program": "repro.session.submit",
            "repro.session.detach.program": "repro.session.detach",
            "repro.session.detach.read": "repro.session.detach",
            "repro.session.run.chunk": "repro.session.run",
            "repro.session.run.chunk.wait": "repro.session.run.chunk",
            "repro.session.run.readout": "repro.session.run"}
    for child, parent in nest.items():
        assert by[child] and all(_inside(c, by[parent]) for c in by[child])
    outer = by["bench.outer"]
    assert all(_inside(ev, outer) for ev in evs if ev[0] != "bench.outer")
    # one job's submit and detach join on (slot, gen)
    keys = {(e[3]["slot"], e[3]["gen"]) for e in by["repro.session.submit"]}
    assert keys == {(e[3]["slot"], e[3]["gen"])
                    for e in by["repro.session.detach"]}
    assert len(keys) == 3


@pytest.mark.parametrize("policy", [TwoLevel(), Fused(steps_per_sync=2)],
                         ids=["host", "device"])
def test_pairs_streamed_is_the_kernel_grid(policy, monkeypatch):
    """Sixteen jobs in chunks of 8 (a VMEM budget that fits 8, not 16)
    over a pair list split into PAIR_CHUNK calls of 16: the kernel's grid
    pair steps, counted where Pallas receives them, per superstep."""
    vb = 32
    monkeypatch.setattr(common, "VMEM_BUDGET",
                        block_vmem_bytes(8, vb, "plus_times"))
    monkeypatch.setattr(fused_kernel, "PAIR_CHUNK", 16)
    grids = []
    real = fused_kernel.pl.pallas_call

    def pallas_call(*args, **kw):
        grids.append(tuple(kw["grid_spec"].grid))
        return real(*args, **kw)

    monkeypatch.setattr(fused_kernel.pl, "pallas_call", pallas_call)
    jax.clear_caches()       # the kernel's jit must trace here
    sess = GraphSession(CSR, vb, capacity=16, seed=3, use_pallas=True)
    for s in range(16):
        sess.submit(PersonalizedPageRank(source=s))
    m = sess.run(policy, 3)
    num_pairs = sess._pair_data(sess.view_groups()[0]).num_pairs
    calls = -(-num_pairs // 16)
    assert calls > 1 and grids and len(grids) % calls == 0
    traces = len(grids) // calls
    per_superstep = sum(a * b for a, b in grids) // traces
    assert {a for a, _ in grids} == {2}
    assert per_superstep == 2 * num_pairs
    assert m.supersteps == 3
    assert m.pairs_streamed == m.supersteps * per_superstep
    assert m.to_dict()["pairs_streamed"] == m.pairs_streamed


def test_digest_keeps_every_update_across_threads(tmp_path):
    """More threads than cores, switching often: nested spans and counts
    from each land in the digest exactly once, and each thread's self
    time stays its own."""
    n_threads, n_iter = 2 * (os.cpu_count() or 4), 300
    errors = []

    def work():
        try:
            for _ in range(n_iter):
                with trace.span("stress.outer"):
                    with trace.span("stress.inner"):
                        trace.count("stress", 1)
        except Exception as e:      # reported below, after the joins
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with profiled(tmp_path):
            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    d = trace.digest()
    total = n_threads * n_iter
    assert d["counters"]["stress"] == total
    outer, inner = d["spans"]["stress.outer"], d["spans"]["stress.inner"]
    assert outer["count"] == inner["count"] == total
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], rel=1e-6, abs=1e-9)
