"""Closed loop of batches: `batch` concurrent queries, run under the
paper's two-level policy on the device driver (`TwoLevel(backend="device",
steps_per_sync=inf)`) to their joint fixpoint; every result is read back,
and the next batch is submitted.  The window runs whole batches until
`seconds` have passed.

The queries are the configuration's search keys, in an order drawn from
the seed, taken `batch` at a time round the cycle: every seed offers the
same queries in another order (64 keys in batches of 64: the same batch
each time, as Graph500 runs its 64 keys).

Traffic keys: kind "closed_batch", batch (queries per batch, also the
session's job capacity).
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench import harness
from bench.graph500 import search_keys
from bench.harness import Query

MAX_SUPERSTEPS = 100_000


class Driver:
    def __init__(self, graph, config, traffic, alg, rng, rec):
        self.graph, self.config, self.alg = graph, config, alg
        self.batch = self.jobs = int(traffic["batch"])
        self.rng, self.rec = rng, rec
        self._queries = []

    def set_up(self) -> None:
        import jax
        from repro.core import TwoLevel
        self.policy = TwoLevel(backend="device", steps_per_sync=math.inf)
        self.order = self.rng.permutation(self.graph.keys)
        self.next = 0
        warm = search_keys(self.graph, self.batch, self.rng)
        with self.rec.span("view_build"):
            self.sess = harness.make_session(self.graph, self.config,
                                             self.batch, self.rng)
            handles = [self.sess.submit(self.alg.job(warm[0], self.config))]
            jax.block_until_ready(
                [g.graph.tiles for g in self.sess.view_groups()])
        # every slot once through submit / run / detach: each slot index
        # is a program of its own in the session's read-back
        with self.rec.span("warm_up.submit"):
            handles += [self.sess.submit(self.alg.job(r, self.config))
                        for r in warm[1:]]
        with self.rec.span("warm_up.run"):
            self.sess.run(self.policy, max_supersteps=1)
        with self.rec.span("warm_up.read_back"):
            for h in handles:
                self.sess.detach(h)

    def run_window(self, seconds: float) -> float:
        t0 = time.perf_counter()
        while True:
            roots = self.order[
                np.arange(self.next, self.next + self.batch)
                % len(self.order)]
            self.next += self.batch
            due = time.perf_counter() - t0
            with self.rec.span("submit"):
                handles = [self.sess.submit(self.alg.job(r, self.config))
                           for r in roots]
            with self.rec.span("run"):
                m = self.sess.run(self.policy, max_supersteps=MAX_SUPERSTEPS)
            self.rec.add(m)
            self.rec.batches.append({"supersteps": m.supersteps,
                                     "tile_pair_loads": m.tile_pair_loads,
                                     "converged": m.converged})
            with self.rec.span("read_back"):
                for r, h in zip(roots, handles):
                    res = self.sess.detach(h)
                    self._queries.append(Query(
                        source=int(r), due_s=due, submit_s=due,
                        done_s=(time.perf_counter() - t0
                                if m.converged else None),
                        result=res))
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                return elapsed

    def queries(self):
        return self._queries

    def close(self) -> None:
        del self.sess
