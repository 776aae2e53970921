"""Faults planted under the timed path, for checks that a run whose
program is broken comes out not correct: a step that returns its state
unchanged, half of the jobs left out of the push, an answer altered where
it is read back.  (The cells run on one chip: there is no exchange
between chips to leave out.)

Each takes `patch(obj, name, value)`, which replaces an attribute of the
program's `GraphSession` (pytest's `monkeypatch.setattr`, or `setattr`
in a process that runs nothing else afterwards).
"""

from __future__ import annotations

import numpy as np


def unchanged(patch) -> None:
    """`run` reports one converged superstep and leaves every job's
    state as it was."""
    from repro.core.policy import RunMetrics
    from repro.core.session import GraphSession
    patch(GraphSession, "run",
          lambda self, policy=None, max_supersteps=0, **kw:
          RunMetrics(supersteps=1, converged=True))


def half_left_out(patch) -> None:
    """After each `run`, the odd job slots get back the state they had
    before it: half of the batch never moves."""
    from repro.core.session import GraphSession
    real = GraphSession.run

    def run(self, policy=None, max_supersteps=100000, **kw):
        keep = [(g, g.values, g.deltas) for g in self.view_groups()]
        m = real(self, policy, max_supersteps, **kw)
        for g, v, d in keep:
            g.values = g.values.at[1::2].set(v[1::2])
            g.deltas = g.deltas.at[1::2].set(d[1::2])
        return m

    patch(GraphSession, "run", run)


def answer_altered(patch) -> None:
    """`detach` returns each result with its largest finite entry made
    1 % larger."""
    from repro.core.session import GraphSession
    real = GraphSession.detach

    def detach(self, handle):
        res = np.array(real(self, handle))
        fin = np.isfinite(res)
        i = int(np.flatnonzero(fin)[np.argmax(res[fin])])
        res[i] *= 1.01
        return res

    patch(GraphSession, "detach", detach)


FAULTS = {f.__name__: f for f in (unchanged, half_left_out, answer_altered)}
