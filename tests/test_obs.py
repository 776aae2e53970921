"""repro.obs: per-superstep telemetry, trace export, serve latency SLIs.

The tentpole acceptance surface:
  * a device run with steps_per_sync=inf AND telemetry on still syncs
    exactly once — and returns a per-superstep series covering EVERY
    superstep (the series rides the scan carry);
  * telemetry-on fixpoints are bitwise identical to telemetry-off, both
    backends (observation never perturbs);
  * host and device backends record IDENTICAL series on a fixed seed for
    all four policies (the graph is small enough that q saturates, so no
    sampling divergence between the numpy RNG and fold_in keys);
  * the telemetry-off compiled superstep is byte-for-byte the cached
    pre-observability program: the jit-cache key carries the capacity, so
    toggling telemetry neither invalidates nor re-traces the other
    variant;
  * Selection counter dtypes are pinned (host: python int; device: int32
    scalars);
  * exported traces are valid Chrome trace-event JSON (schema-checked)
    and carry the submit/detach/apply_updates story;
  * ConcurrentServeScheduler records deterministic wait_steps and
    p50/p99 summaries.
"""

import json
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.algorithms import PageRank, PersonalizedPageRank, SSSP
from repro.core import Fused, GraphSession, TwoLevel
from repro.core.policy import AllBlocks, Independent
from repro.graph import rmat_graph
from repro.obs import (TelemetryConfig, TelemetrySeries, SERIES_FIELDS,
                       validate_trace_events)
from repro.serve.concurrent import (ConcurrentServeScheduler, Request,
                                    RequestStream)
from repro.stream import UpdateBatch

CSR = rmat_graph(300, 5, seed=7)

ALL_POLICIES = [TwoLevel, Independent, AllBlocks, Fused]


def _session(telemetry=True, **kw):
    sess = GraphSession(CSR, 32, capacity=2, seed=3, telemetry=telemetry,
                        **kw)
    sess.submit(PageRank())
    sess.submit(SSSP(source=0))
    return sess


# --- config coercion --------------------------------------------------------


def test_telemetry_config_coercion():
    assert TelemetryConfig.coerce(None) is None
    assert TelemetryConfig.coerce(False) is None
    assert TelemetryConfig.coerce(True) == TelemetryConfig()
    cfg = TelemetryConfig(capacity=16, trace=False)
    assert TelemetryConfig.coerce(cfg) is cfg
    with pytest.raises(TypeError):
        TelemetryConfig.coerce(42)


def test_telemetry_off_session_records_nothing():
    sess = _session(telemetry=None)
    m = sess.run(TwoLevel(), 500)
    assert m.converged and m.telemetry is None
    assert not sess.trace.enabled and sess.trace.events == []
    # a disabled recorder still exports a valid (metadata-only) trace
    validate_trace_events(sess.trace.to_json())


# --- the series itself ------------------------------------------------------


def test_host_series_covers_every_superstep_and_sums_to_totals():
    sess = _session()
    m = sess.run(TwoLevel(), 500)
    tel = m.telemetry
    assert isinstance(tel, TelemetrySeries)
    assert len(tel) == m.supersteps and not tel.truncated
    assert int(tel.tile_loads.sum()) == m.tile_loads
    assert int(tel.job_block_pushes.sum()) == m.job_block_pushes
    assert tel.num_groups == 2       # plus_times + min_plus views
    # supersteps run while work remains: active_jobs >= 1 throughout, and
    # unconverged is monotone-ish to zero at the end (last row may still
    # be nonzero — it describes the state BEFORE the final push)
    assert (tel.active_jobs >= 1).all()
    assert (tel.unconverged[0] > 0).all()
    assert (tel.max_residual >= 0).all()
    # dirty_blocks is zero without apply_updates
    assert (tel.dirty_blocks == 0).all()


def test_device_inf_full_series_at_exactly_one_sync():
    """THE tentpole invariant: steps_per_sync=inf + telemetry returns the
    complete per-superstep series while host_syncs stays 1."""
    sess = _session()
    m = sess.run(TwoLevel(backend="device", steps_per_sync=math.inf), 500)
    assert m.converged
    assert m.host_syncs == 1
    tel = m.telemetry
    assert len(tel) == m.supersteps and not tel.truncated
    assert int(tel.tile_loads.sum()) == m.tile_loads
    assert int(tel.job_block_pushes.sum()) == m.job_block_pushes


@pytest.mark.parametrize("policy_cls", ALL_POLICIES)
def test_host_and_device_record_identical_series(policy_cls):
    """Fixed seed, q saturated (every live block fits the queue, so the
    host numpy RNG and the device fold_in keys never actually sample):
    both backends must log the SAME schedule, column for column."""
    sess_h = _session()
    sess_d = _session()
    if policy_cls is Fused:
        m_h = sess_h.run(TwoLevel(), 500)
        m_d = sess_d.run(Fused(), 500)
    else:
        m_h = sess_h.run(policy_cls(), 500)
        m_d = sess_d.run(policy_cls(backend="device"), 500)
    assert m_h.converged and m_d.converged
    assert m_h.supersteps == m_d.supersteps
    t_h, t_d = m_h.telemetry, m_d.telemetry
    for f in SERIES_FIELDS:
        np.testing.assert_array_equal(getattr(t_h, f), getattr(t_d, f),
                                      err_msg=f)
    np.testing.assert_array_equal(t_h.unconverged, t_d.unconverged)
    np.testing.assert_allclose(t_h.max_residual, t_d.max_residual,
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("kw", [dict(),
                                dict(backend="device"),
                                dict(backend="device",
                                     steps_per_sync=math.inf)])
def test_telemetry_does_not_perturb_the_fixpoint(kw):
    """Bitwise: values/deltas after a telemetry-on run equal the
    telemetry-off run's, every backend/cadence."""
    sess_on, sess_off = _session(True), _session(None)
    m_on = sess_on.run(TwoLevel(**kw), 500)
    m_off = sess_off.run(TwoLevel(**kw), 500)
    assert m_on.converged and m_off.converged
    assert m_on.supersteps == m_off.supersteps
    assert m_on.tile_loads == m_off.tile_loads
    for g_on, g_off in zip(sess_on.view_groups(), sess_off.view_groups()):
        np.testing.assert_array_equal(np.asarray(g_on.values),
                                      np.asarray(g_off.values))
        np.testing.assert_array_equal(np.asarray(g_on.deltas),
                                      np.asarray(g_off.deltas))


def test_device_capacity_truncation_keeps_converging():
    """A run longer than the buffer still converges; the series holds the
    first `capacity` rows and is flagged truncated."""
    sess = _session(TelemetryConfig(capacity=8))
    m = sess.run(TwoLevel(backend="device", steps_per_sync=math.inf), 500)
    assert m.converged and m.supersteps > 8
    tel = m.telemetry
    assert tel.truncated and len(tel) == 8
    # the prefix matches an untruncated run's
    full = _session().run(
        TwoLevel(backend="device", steps_per_sync=math.inf), 500).telemetry
    np.testing.assert_array_equal(tel.tile_loads[:7], full.tile_loads[:7])


def test_dirty_blocks_series_spikes_once_after_apply_updates():
    sess = _session()
    assert sess.run(TwoLevel(), 500).converged
    sess.apply_updates(UpdateBatch.inserts(
        np.array([1, 2]), np.array([5, 9]), np.array([1.0, 1.0])))
    m = sess.run(TwoLevel(), 500)
    tel = m.telemetry
    assert m.dirty_blocks > 0
    assert int(tel.dirty_blocks[0]) == m.dirty_blocks
    assert (tel.dirty_blocks[1:] == 0).all()


# --- compiled-out when off: the jit cache stays pinned ----------------------


def test_telemetry_off_superstep_cache_is_untouched(transfer_sentinel,
                                                    retrace_pin):
    """Off-session: the cache key ends in capacity 0 and re-running never
    re-traces (same _cache_size pin as the device-scheduler suite); the
    re-run is additionally pinned by the analysis sentinels — explicit
    syncs only, zero cache growth."""
    sess = _session(telemetry=None)
    assert sess.run(Fused(), 500).converged
    with retrace_pin(sess):
        assert sess.run(Fused(), 500).converged
    entries = [k for k in sess._jit_cache if k[0] == "superstep"]
    assert len(entries) == 1 and entries[0][-1] == 0
    assert sess._jit_cache[entries[0]]._cache_size() == 1


def test_telemetry_on_compiles_its_own_entry_without_retracing(
        retrace_pin):
    sess = _session(TelemetryConfig(capacity=64))
    assert sess.run(Fused(), 500).converged
    with retrace_pin(sess):
        assert sess.run(Fused(), 500).converged
    entries = [k for k in sess._jit_cache if k[0] == "superstep"]
    assert len(entries) == 1 and entries[0][-1] == 64
    assert sess._jit_cache[entries[0]]._cache_size() == 1


# --- Selection dtype contract -----------------------------------------------


@pytest.mark.parametrize("policy_cls", [TwoLevel, Independent, AllBlocks])
def test_selection_counter_dtypes(policy_cls):
    """Host select returns python ints; device_select returns int32
    scalars — the drivers coerce exactly once (see Selection docstring)."""
    sess = _session(telemetry=None)
    groups = sess.view_groups()
    node_un, p_mean, actives = [], [], []
    for g in groups:
        nu, pm = map(np.asarray, sess._pairs_fn(g)(g.values, g.deltas))
        node_un.append(nu)
        p_mean.append(pm)
        actives.append(nu.sum(-1) > 0)
    selection = policy_cls().select(
        sess, node_un if policy_cls.needs_pairs else
        [nu.sum(-1) for nu in node_un], p_mean, actives)
    assert type(selection.tile_loads) is int
    assert type(selection.job_block_pushes) is int

    nus = [jnp.asarray(nu, jnp.float32) for nu in node_un]
    pms = [jnp.asarray(pm, jnp.float32) for pm in p_mean]
    acts = [jnp.asarray(a) for a in actives]
    d_sel = policy_cls(backend="device").device_select(
        nus, pms, acts, jax.random.PRNGKey(0), q=sess.q,
        alpha=sess.alpha, samples=sess.samples,
        num_blocks=sess.scheduler.num_blocks)
    assert d_sel.tile_loads.dtype == jnp.int32
    assert d_sel.job_block_pushes.dtype == jnp.int32


# --- RunMetrics surface -----------------------------------------------------


def test_run_metrics_to_dict_and_wall_time():
    sess = _session()
    m = sess.run(TwoLevel(), 500)
    assert m.wall_time_s > 0
    d = m.to_dict()
    assert d["supersteps"] == m.supersteps
    assert d["host_syncs"] == m.host_syncs
    assert d["converged"] is True
    assert "telemetry" not in d
    full = m.to_dict(include_telemetry=True)
    assert full["telemetry"]["supersteps"] == m.supersteps
    json.dumps(full)    # JSON-ready all the way down


# --- trace export -----------------------------------------------------------


def test_trace_export_is_valid_chrome_trace_json(tmp_path):
    sess = _session()
    h = sess.submit(PersonalizedPageRank(source=9))
    assert sess.run(TwoLevel(), 500).converged
    sess.apply_updates(UpdateBatch.inserts(
        np.array([0]), np.array([7]), np.array([1.0])))
    assert sess.run(TwoLevel(), 500).converged
    sess.detach(h)
    path = tmp_path / "trace.json"
    sess.trace.export(str(path))
    doc = json.loads(path.read_text())
    assert validate_trace_events(doc) == len(doc["traceEvents"])
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"session.submit", "session.detach", "session.run", "superstep",
            "apply_updates", "converged", "process_name"} <= names
    # submit and detach are spans with a duration; (view, slot, gen)
    # joins the PPR job's two
    jobs = [e for e in doc["traceEvents"]
            if e["name"] in ("session.submit", "session.detach")]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in jobs)
    key = (h.view, h.slot, h.gen)
    assert [e["name"] for e in jobs
            if (tuple(e["args"]["view"]), e["args"]["slot"],
                e["args"]["gen"]) == key] == ["session.submit",
                                              "session.detach"]
    # per-superstep spans landed on the named superstep track
    spans = [e for e in doc["traceEvents"] if e["name"] == "superstep"]
    assert spans and all(e["ph"] == "X" and e["dur"] >= 0 for e in spans)
    # counter samples carry the full fixed schema
    counters = [e for e in doc["traceEvents"]
                if e["name"] == "telemetry" and e["ph"] == "C"]
    assert counters and set(SERIES_FIELDS) <= set(counters[0]["args"])


def test_trace_schema_validator_rejects_malformed_events():
    with pytest.raises(ValueError):
        validate_trace_events({"events": []})
    with pytest.raises(ValueError):
        validate_trace_events(
            {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0,
                              "pid": 1, "tid": 1}]})  # X without dur
    with pytest.raises(ValueError):
        validate_trace_events(
            {"traceEvents": [{"name": "x", "ph": "B", "ts": 0.0,
                              "pid": 1, "tid": 1}]})  # unknown phase


def test_device_chunks_traced_per_sync():
    sess = _session()
    m = sess.run(TwoLevel(backend="device", steps_per_sync=8), 500)
    chunks = [e for e in sess.trace.events
              if e["name"] == "session.run.chunk"]
    assert len(chunks) == m.host_syncs


# --- serve-layer metrics ----------------------------------------------------


def _serve_world():
    sched = ConcurrentServeScheduler(8, 4, seed=0)
    chat = RequestStream(0, family="chat")
    batch = RequestStream(1, family="batch")
    sched.add_stream(chat)
    sched.add_stream(batch)
    for i in range(10):
        chat.add(Request(0, i % 8, 1.0, 4))
        batch.add(Request(1, i % 8, 0.5, 4))
    return sched, chat, batch


def test_serve_metrics_percentiles_and_families():
    sched, chat, batch = _serve_world()
    done = []
    while chat.waiting or batch.waiting:
        done += sched.schedule_step()
    for r in done:
        sched.complete(r, service_s=0.01)
    s = sched.metrics.summary()
    assert s["steps"] == sched._step_idx >= 5     # 20 reqs / budget 4
    assert s["wait_steps"]["count"] == 20
    assert 0 <= s["wait_steps"]["p50"] <= s["wait_steps"]["p99"] \
        <= s["wait_steps"]["max"]
    assert s["service_s"]["count"] == 20
    assert abs(s["service_s"]["p50"] - 0.01) < 1e-9
    assert set(s["queue_depth_by_family"]) == {"chat", "batch"}
    assert set(s["wait_steps_by_stream"]) == {"0", "1"}
    assert len(sched.metrics.gq_occupancy) == s["steps"]
    json.dumps(s)


def test_serve_wait_steps_are_deterministic():
    """wait_steps counts scheduler steps (not wall time), so two identical
    worlds record identical samples."""
    runs = []
    for _ in range(2):
        sched, chat, batch = _serve_world()
        while chat.waiting or batch.waiting:
            sched.schedule_step()
        runs.append(sorted(sched.metrics.wait_steps.samples))
    assert runs[0] == runs[1]
    # budget 4, 20 requests: someone waited, nobody waited forever
    assert runs[0][0] == 0 and 0 < runs[0][-1] <= 5


def test_serve_metrics_opt_out():
    sched = ConcurrentServeScheduler(4, 2, metrics=False)
    st = RequestStream(0)
    sched.add_stream(st)
    st.add(Request(0, 0, 1.0, 1))
    assert sched.metrics is None
    assert len(sched.schedule_step()) == 1      # scheduling unaffected


def test_serve_admissions_land_on_a_shared_trace():
    sess = _session()
    sched = ConcurrentServeScheduler(4, 2, trace=sess.trace)
    st = RequestStream(0)
    sched.add_stream(st)
    st.add(Request(0, 0, 1.0, 1))
    sched.schedule_step()
    ev = [e for e in sess.trace.events if e["name"] == "serve.admit"]
    assert len(ev) == 1 and ev[0]["args"]["admitted"] == 1
