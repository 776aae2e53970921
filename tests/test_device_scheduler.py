"""Device-resident scheduling: the jnp analogues must match the host core.

Covers the tentpole acceptance surface:
  * do_select_device is DISTRIBUTIONALLY equivalent to the host sampler
    (per-block selection frequencies over >=1k draws);
  * global_queue_device agrees with the host synthesis on the
    reserved-head-slot edge cases (the Fig. 7 invariants);
  * TwoLevelScheduler/serve keep one core across backend="host"|"device";
  * the compiled superstep is CACHED on the session (no re-trace across
    run() calls, resubmissions, recycled slots);
  * steps_per_sync amortizes host round-trips without changing the
    schedule (same supersteps/tile_loads, >=4x fewer syncs at K=8).
"""

import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.algorithms import PageRank, PersonalizedPageRank, SSSP
from repro.core import (Fused, GraphSession, TwoLevel, TwoLevelScheduler,
                        do_select, do_select_device, global_queue,
                        global_queue_device)
from repro.graph import rmat_graph
from repro.serve.concurrent import (ConcurrentServeScheduler, Request,
                                    RequestStream)

CSR = rmat_graph(300, 5, seed=7)


# --- Function 2: device sampler vs host sampler -----------------------------


def _frequencies(node_un, p_mean, q, s, draws):
    freq_h = np.zeros(len(node_un))
    for i in range(draws):
        out = do_select(node_un, p_mean, q, np.random.default_rng(1000 + i),
                        s)
        freq_h[out] += 1
    sel, msk = jax.vmap(lambda k: do_select_device(
        jnp.asarray(node_un, jnp.float32), jnp.asarray(p_mean, jnp.float32),
        q, k, s))(jax.random.split(jax.random.PRNGKey(0), draws))
    sel, msk = np.asarray(sel), np.asarray(msk)
    freq_d = np.zeros(len(node_un))
    for i in range(draws):
        freq_d[sel[i][msk[i] > 0]] += 1
    return freq_h / draws, freq_d / draws


def test_device_sampler_matches_host_selection_frequencies():
    """>=1k draws each: the per-block marginal selection frequency of the
    device sampler must track the host sampler's.  Means are placed in
    distinct log-buckets so the exact CBP comparator and its scalar
    surrogate rank identically — what remains is pure sampling-threshold
    randomness, the thing being compared."""
    rng = np.random.default_rng(3)
    b_n, q, s, draws = 64, 6, 16, 1200
    node_un = rng.integers(0, 30, b_n).astype(np.float64)
    p_mean = np.where(node_un > 0, 2.0 ** rng.integers(-3, 9, b_n),
                      0.0).astype(np.float64)
    freq_h, freq_d = _frequencies(node_un, p_mean, q, s, draws)
    # marginals agree per block and in total queue mass
    assert np.abs(freq_h - freq_d).max() < 0.08
    assert abs(freq_h.sum() - freq_d.sum()) < 0.05 * max(freq_h.sum(), 1)
    # the certainly-hot blocks are certain under both samplers
    np.testing.assert_array_equal(freq_h > 0.99, freq_d > 0.99)


def test_device_sampler_degenerate_cases_match_host_exactly():
    key = jax.random.PRNGKey(0)
    # all converged -> empty queue
    sel, msk = do_select_device(jnp.zeros(10), jnp.zeros(10), 3, key)
    assert msk.sum() == 0
    # fewer live blocks than q -> the whole live set, no sampling
    node_un = np.zeros(20)
    p_mean = np.zeros(20)
    node_un[[3, 11, 17]] = [5.0, 2.0, 9.0]
    p_mean[[3, 11, 17]] = [1.0, 8.0, 64.0]
    sel, msk = do_select_device(jnp.asarray(node_un, jnp.float32),
                                jnp.asarray(p_mean, jnp.float32), 8, key)
    got = set(np.asarray(sel)[np.asarray(msk) > 0].tolist())
    want = set(do_select(node_un, p_mean, 8,
                         np.random.default_rng(0)).tolist())
    assert got == want == {3, 11, 17}
    # the hot block heads the queue
    assert int(sel[0]) == 17


# --- Fig. 7: device synthesis vs host synthesis -----------------------------


def _dev_gq(job_queues, num_blocks, q, alpha=0.8):
    j = max(1, len(job_queues))
    sel = np.zeros((j, q), np.int32)
    msk = np.zeros((j, q), np.float32)
    for i, jq in enumerate(job_queues):
        L = min(len(jq), q)
        sel[i, :L] = jq[:L]
        msk[i, :L] = 1.0
    gsel, gmsk = global_queue_device(jnp.asarray(sel), jnp.asarray(msk),
                                     num_blocks, q, alpha)
    gsel, gmsk = np.asarray(gsel), np.asarray(gmsk)
    return gsel[gmsk > 0]


def test_device_synthesis_reserves_individual_heads():
    """The edge case the reserved (1-alpha)q slots exist for: a singleton
    queue's head must enter the global queue although its cumulative
    weight loses to every shared block — and the selected SET must match
    the host synthesis exactly."""
    jq = [np.arange(1, 9), np.arange(1, 9), np.array([9])]
    host = global_queue(jq, num_blocks=12, q=8, alpha=0.8)
    dev = _dev_gq(jq, num_blocks=12, q=8, alpha=0.8)
    assert 9 in dev.tolist()
    assert set(dev.tolist()) == set(host.tolist())
    assert len(set(dev.tolist())) == len(dev)      # no duplicates


def test_device_synthesis_many_heads_never_crowd_out_weighted_slots():
    """16 jobs with 16 distinct queue heads compete for 2 reserved slots
    (q=10, alpha=0.8): the ceil(alpha*q)=8 cumulative-weight winners must
    ALL survive — the reserved mechanism may only claim its (1-alpha)q
    quota — and the device set must equal the host set exactly.  (A naive
    'boost every head' rendering fails this: 10 heads would fill the
    whole queue.)"""
    jq = [np.array([40 + j, 0, 1, 2, 3, 4, 5, 6, 7]) for j in range(16)]
    host = global_queue(jq, num_blocks=64, q=10, alpha=0.8)
    dev = _dev_gq(jq, num_blocks=64, q=10, alpha=0.8)
    assert set(dev.tolist()) == set(host.tolist())
    # the 8 weight-ranked blocks all present, exactly 2 reserved heads
    assert set(range(8)) <= set(dev.tolist())
    assert len([b for b in dev.tolist() if b >= 40]) == 2
    assert len(dev) == 10


def test_device_synthesis_duplicate_heads_counted_once_and_first():
    jq = [np.array([7, 1]), np.array([7, 2]), np.array([7, 3])]
    host = global_queue(jq, num_blocks=10, q=4)
    dev = _dev_gq(jq, num_blocks=10, q=4)
    assert dev[0] == host[0] == 7
    assert list(dev).count(7) == 1
    assert set(dev.tolist()) == set(host.tolist())


def test_device_synthesis_alpha_one_has_no_reserved_slots():
    jq = [np.array([1, 2, 3, 4]), np.array([1, 2, 3, 4]), np.array([9])]
    host = global_queue(jq, num_blocks=12, q=4, alpha=1.0)
    dev = _dev_gq(jq, num_blocks=12, q=4, alpha=1.0)
    assert dev[0] == host[0] == 1
    assert set(dev.tolist()) == set(host.tolist())


def test_device_synthesis_alpha_zero_keeps_one_weighted_slot():
    """Host floor: n_global = max(1, ceil(alpha*q)), so even alpha=0 must
    keep the top cumulative-weight block; heads take only the rest."""
    jq = [np.array([10 + j, 1, 2, 3]) for j in range(5)]
    host = global_queue(jq, num_blocks=16, q=2, alpha=0.0)
    dev = _dev_gq(jq, num_blocks=16, q=2, alpha=0.0)
    assert set(dev.tolist()) == set(host.tolist())
    assert 1 in dev.tolist()      # the weighted winner survives


def test_device_run_advances_the_sampling_stream_across_runs():
    """Host semantics: the scheduler RNG advances across run()/step()
    calls (only the legacy shim resets per call).  The device backend
    must advance its fold_in stream position the same way, or an
    arrival-model loop of step() calls would replay one sample forever."""
    sess = GraphSession(CSR, 32, capacity=2, seed=5)
    sess.submit(PageRank())
    pos0 = sess.scheduler._step
    m1 = sess.run(TwoLevel(backend="device"), max_supersteps=5)
    assert sess.scheduler._step == pos0 + m1.supersteps
    m2 = sess.run(TwoLevel(backend="device"), 20000)
    assert m2.converged
    assert sess.scheduler._step == pos0 + m1.supersteps + m2.supersteps


def test_device_synthesis_short_and_empty_queues():
    jq = [np.array([3]), np.array([5])]
    assert set(_dev_gq(jq, 8, 4, alpha=1.0).tolist()) == {3, 5}
    assert len(_dev_gq([np.empty(0, np.int64)], 5, 3)) == 0


# --- one scheduler core, pluggable backend ----------------------------------


def test_scheduler_backend_device_keeps_the_list_interface():
    """Same object, same select() contract: when the candidate set fits
    the queue (no sampling randomness) both backends pick the same set."""
    node_un = np.zeros((2, 16))
    p_mean = np.zeros((2, 16))
    node_un[0, [1, 4]] = [3.0, 9.0]
    p_mean[0, [1, 4]] = [2.0, 16.0]
    node_un[1, [4, 9]] = [7.0, 2.0]
    p_mean[1, [4, 9]] = [16.0, 0.5]
    out = {}
    for backend in ("host", "device"):
        sched = TwoLevelScheduler(16, 4, seed=0, backend=backend)
        queues, gq = sched.select(node_un, p_mean)
        assert len(queues) == 2
        assert all(len(set(jq.tolist())) == len(jq) for jq in queues)
        out[backend] = set(gq.tolist())
    assert out["host"] == out["device"] == {1, 4, 9}


def test_scheduler_backend_validation_and_reset():
    with pytest.raises(ValueError):
        TwoLevelScheduler(8, 2, backend="gpu")
    sched = TwoLevelScheduler(8, 2, seed=3, backend="device")
    sched._next_key()
    assert sched._step == 1
    sched.reset()
    assert sched._step == 0


def test_serve_scheduler_runs_on_the_device_backend():
    """The serve layer inherits the device core with zero serve-side code:
    the shared hot group still serves both streams within budget."""
    sched = ConcurrentServeScheduler(n_groups=8, batch_budget=2, seed=0,
                                     backend="device")
    s1, s2 = RequestStream(1), RequestStream(2)
    sched.add_stream(s1)
    sched.add_stream(s2)
    s1.add(Request(1, 5, urgency=9.0, tokens_left=5))
    s2.add(Request(2, 5, urgency=9.0, tokens_left=5))
    admitted = sched.schedule_step()
    assert len(admitted) == 2
    assert {r.stream_id for r in admitted} == {1, 2}
    assert all(r.group == 5 for r in admitted)


# --- policy knobs ------------------------------------------------------------


def test_policy_backend_and_steps_per_sync_validation():
    with pytest.raises(ValueError):
        TwoLevel(backend="gpu")
    with pytest.raises(ValueError):
        TwoLevel(steps_per_sync=4)            # host syncs every superstep
    with pytest.raises(ValueError):
        TwoLevel(backend="device", steps_per_sync=0)
    with pytest.raises(ValueError):
        TwoLevel(backend="device", steps_per_sync=2.5)
    assert Fused().steps_per_sync == math.inf
    assert Fused(steps_per_sync=4).steps_per_sync == 4
    assert Fused().backend == "device"


def test_superstep_compiles_once_across_runs_and_resubmissions(
        transfer_sentinel, retrace_pin):
    """Satellite: the old Fused.run re-traced its while_loop every call.
    The compiled step must be cached on the session and survive run(),
    resubmission into a recycled slot, and detach — one cache entry, and
    jax must not re-trace (pinned via jax's own lowering counter).  The
    whole scenario runs under the transfer sentinel (every sync must be
    an explicit device_get) and runs 2-3 under the retrace sentinel."""
    sess = GraphSession(CSR, 32, capacity=2, seed=5)
    # submit / detach compile one program per (view, class, static
    # fields) on first use: warm the ones the pinned block calls, so
    # that it compiles nothing at all
    for alg in (PageRank(), PageRank(damping=0.6)):
        sess.detach(sess.submit(alg))
    h0 = sess.submit(PageRank())
    assert sess.run(Fused(), 20000).converged
    sess.submit(PersonalizedPageRank(source=7))     # same capacity
    with retrace_pin(sess):
        assert sess.run(Fused(), 20000).converged
        sess.detach(h0)
        sess.submit(PageRank(damping=0.6))          # recycled slot
        assert sess.run(Fused(), 20000).converged
    entries = [k for k in sess._jit_cache if k[0] == "superstep"]
    assert len(entries) == 1
    # three runs, one compilation: the jit object's trace cache holds a
    # single entry (shapes/dtypes never changed across runs)
    assert sess._jit_cache[entries[0]]._cache_size() == 1


def test_steps_per_sync_amortizes_host_round_trips():
    """Acceptance: K=8 cuts scheduling round-trips >=4x vs K=1 while the
    schedule itself is unchanged (same key stream fold_in(seed, step), so
    identical supersteps AND tile_loads)."""
    algs = [PageRank(), PersonalizedPageRank(source=7)]
    ms = {}
    for k in (1, 8):
        sess = GraphSession(CSR, 32, capacity=2, seed=5)
        for a in algs:
            sess.submit(a)
        ms[k] = sess.run(TwoLevel(backend="device", steps_per_sync=k),
                         20000)
    assert ms[1].converged and ms[8].converged
    assert ms[1].supersteps == ms[8].supersteps
    assert ms[1].tile_loads == ms[8].tile_loads
    assert ms[1].job_block_pushes == ms[8].job_block_pushes
    assert ms[1].host_syncs >= 4 * ms[8].host_syncs


def test_host_backend_reports_one_sync_per_superstep():
    sess = GraphSession(CSR, 32, capacity=2, seed=5)
    sess.submit(PageRank())
    m = sess.run(TwoLevel(), 20000)
    assert m.converged
    # one scheduling sync per superstep + the final all-converged poll
    assert m.host_syncs == m.supersteps + 1


def test_device_backend_never_pushes_a_converged_group():
    """The host driver's invariant — a fully-converged view group is never
    pushed, so sub-tolerance plus-times residual mass stays where
    convergence left it — must hold inside the jitted superstep too.
    PageRank(0.5) on a 30x30 grid converges long before SSSP crosses the
    diameter; once it does, further device supersteps must leave its
    group state BIT-identical (without the freeze, residual deltas keep
    scattering and the result drifts toward the tolerance)."""
    from repro.graph import grid_graph
    sess = GraphSession(grid_graph(30), 32, capacity=1, seed=3)
    h_pr = sess.submit(PageRank(damping=0.5))
    h_ss = sess.submit(SSSP(source=0))
    pol = TwoLevel(backend="device")
    for _ in range(500):
        if sess.converged(h_pr):
            break
        sess.run(pol, max_supersteps=1)
    assert sess.converged(h_pr) and not sess.converged(h_ss)
    pt = [g for g in sess.view_groups() if g.semiring == "plus_times"][0]
    snap_v, snap_d = np.asarray(pt.values), np.asarray(pt.deltas)
    sess.run(pol, max_supersteps=10)          # min-plus family still hot
    assert not sess.converged(h_ss)
    np.testing.assert_array_equal(np.asarray(pt.values), snap_v)
    np.testing.assert_array_equal(np.asarray(pt.deltas), snap_d)


def test_fused_and_explicit_device_twolevel_share_one_compilation():
    """Fused() IS TwoLevel(backend='device', steps_per_sync=inf): running
    both on one session must not compile the superstep twice (the cache
    keys on the selection program, not the policy's name)."""
    sess = GraphSession(CSR, 32, capacity=2, seed=5)
    sess.submit(PageRank())
    assert sess.run(Fused(), 20000).converged
    assert sess.run(TwoLevel(backend="device", steps_per_sync=math.inf),
                    20000).converged
    assert len([k for k in sess._jit_cache if k[0] == "superstep"]) == 1


def test_device_two_level_matches_host_fixpoint_fast():
    """Cheap fixed-seed cross-backend check in the fast suite (the full
    policy x backend x cadence grid lives in the slow property suite)."""
    ref_sess = GraphSession(CSR, 32, capacity=2, seed=5)
    r0 = ref_sess.submit(PageRank())
    r1 = ref_sess.submit(SSSP(source=0))
    assert ref_sess.run(TwoLevel(), 20000).converged
    sess = GraphSession(CSR, 32, capacity=2, seed=5)
    h0 = sess.submit(PageRank())
    h1 = sess.submit(SSSP(source=0))
    assert sess.run(TwoLevel(backend="device", steps_per_sync=4),
                    20000).converged
    np.testing.assert_array_equal(sess.result(h1), ref_sess.result(r1))
    np.testing.assert_allclose(sess.result(h0), ref_sess.result(r0),
                               rtol=1e-3, atol=1e-5)
