"""Open loop of single queries on the wall clock.

`round(rate * seconds)` requests are due at seeded times over the window
(`bench.arrivals.open_loop`: Poisson given its count, Zipf sources,
tenants, urgencies).  One host loop serves them through the program's
serve front and session:

  inject    every request now due joins its tenant's `RequestStream`
  admit     `ConcurrentServeScheduler.schedule_step()` with a budget of
            the free slots (at most `max_running` queries run at once);
            each admitted request is `GraphSession.submit`ted
  advance   `run(Fused(), max_supersteps=chunk)` over all running jobs
  retire    `unconverged_counts()`; each converged job is `detach`ed,
            which reads its result back

A request's latency runs from its due time to its read-back.  After the
window closes, the loop drains for up to `bench.harness.DRAIN_S`; a
request still open then has failed.  How late the loop injected each
request (its lateness against its due time) is printed on stderr.

Traffic keys: kind "serve_open", rate (requests/s), zipf_exponent,
n_tenants, max_running (also the session's job capacity), chunk
(supersteps per run call).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from bench import arrivals, harness
from bench.harness import Query
from bench.stats import percentile_summary


class Driver:
    def __init__(self, graph, config, traffic, alg, rng, rec):
        self.graph, self.config, self.traffic, self.alg = (
            graph, config, traffic, alg)
        self.cap = self.jobs = int(traffic["max_running"])
        self.chunk = int(traffic["chunk"])
        self.rng, self.rec = rng, rec
        self._queries = []

    def set_up(self) -> None:
        import jax
        from repro.core import Fused
        from repro.serve.concurrent import ConcurrentServeScheduler
        self.policy = Fused()
        self.candidates = np.flatnonzero(self.graph.degree > 0)
        warm = self.rng.choice(self.candidates, size=self.cap, replace=False)
        with self.rec.span("view_build"):
            self.sess = harness.make_session(self.graph, self.config,
                                             self.cap, self.rng)
            handles = [self.sess.submit(self.alg.job(warm[0], self.config))]
            jax.block_until_ready(
                [g.graph.tiles for g in self.sess.view_groups()])
        # every slot once through submit / run / poll / detach: each slot
        # index is a program of its own in the session's read-back
        with self.rec.span("warm_up.submit"):
            handles += [self.sess.submit(self.alg.job(s, self.config))
                        for s in warm[1:]]
        with self.rec.span("warm_up.run"):
            self.sess.run(self.policy, max_supersteps=self.chunk)
            self.sess.unconverged_counts()
        with self.rec.span("warm_up.read_back"):
            for h in handles:
                self.sess.detach(h)
        self.sched = ConcurrentServeScheduler(
            self.sess.scheduler.num_blocks, batch_budget=self.cap,
            seed=int(self.rng.integers(2**31 - 1)))

    def run_window(self, seconds: float) -> float:
        from repro.serve.concurrent import Request, RequestStream
        t = self.traffic
        todo = arrivals.open_loop(float(t["rate"]), seconds, self.candidates,
                                  float(t["zipf_exponent"]),
                                  int(t["n_tenants"]), self.rng)
        block = self.sess.view_groups()[0].graph.block_size
        queries = [Query(source=a.source, due_s=a.due_s) for a in todo]
        by_req = {}
        lateness = []
        running = {}                  # id(req) -> (request, query, handle)
        cursor = 0
        t0 = time.perf_counter()
        last_done = 0.0
        while True:
            now = time.perf_counter() - t0
            while cursor < len(todo) and todo[cursor].due_s <= now:
                a = todo[cursor]
                if a.tenant not in self.sched.streams:
                    self.sched.add_stream(RequestStream(a.tenant, "ppr"))
                req = Request(stream_id=a.tenant, group=a.source // block,
                              urgency=a.urgency, tokens_left=1)
                by_req[id(req)] = queries[cursor]
                self.sched.streams[a.tenant].add(req)
                lateness.append(now - a.due_s)
                cursor += 1
            waiting = any(s.waiting for s in self.sched.streams.values())
            if cursor == len(todo) and not running and not waiting:
                break
            if now > seconds + harness.DRAIN_S:
                break
            if not running and not waiting:
                time.sleep(max(0.0, min(todo[cursor].due_s - now, 0.05)))
                continue
            self.sched.batch_budget = self.cap - len(running)
            with self.rec.span("admit"):
                for req in self.sched.schedule_step():
                    q = by_req.pop(id(req))
                    h = self.sess.submit(self.alg.job(q.source, self.config))
                    q.submit_s = time.perf_counter() - t0
                    running[id(req)] = (req, q, h)
            if not running:
                continue
            with self.rec.span("run"):
                m = self.sess.run(self.policy, max_supersteps=self.chunk)
            self.rec.add(m)
            with self.rec.span("poll"):
                counts = self.sess.unconverged_counts()
            with self.rec.span("read_back"):
                for key in [k for k, (_, _, h) in running.items()
                            if counts[self.sess.job_index(h)] == 0]:
                    req, q, h = running.pop(key)
                    q.result = self.sess.detach(h)
                    q.done_s = last_done = time.perf_counter() - t0
                    self.sched.complete(req)
        self._queries = queries
        late = percentile_summary(lateness)
        worst = todo[int(np.argmax(lateness))].due_s if lateness else 0.0
        print(f"generator lateness (s): p50 {late['p50']} p95 {late['p95']}"
              f" max {late['max']} (due at {worst:.2f} s) over "
              f"{late['count']} requests", file=sys.stderr)
        lat = percentile_summary([q.done_s - q.due_s for q in queries
                                  if q.done_s is not None])
        print(f"latency (s) of the requests completed: p50 {lat['p50']} "
              f"p95 {lat['p95']} p99 {lat['p99']} max {lat['max']}",
              file=sys.stderr)
        return max(last_done, seconds)

    def queries(self):
        return self._queries

    def close(self) -> None:
        del self.sess, self.sched
