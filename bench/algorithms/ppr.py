"""Personalised PageRank queries: the program's job, the reference, the
control and the number compared.

The engine's result is values + pending deltas at a fixpoint where every
pending delta lies in [0, tol).  In exact arithmetic it then lies in the
band [ref - tol * u, ref] at every vertex, where ref is the true vector
and u = (I - d W^T)^-1 d W^T 1 (`bench.reference.ppr_shortfall`): below
the reference by the mass the tolerance leaves unpushed, never above it.

  ppr_band_rel  the widest departure from that band, relative to ref
                (ref 0 compares absolutely): above it by float32 rounding
                at `Precision.HIGHEST` (~1e-7); products in three
                bfloat16 passes land above it by ~1e-5; a lost, stale or
                altered answer lands far outside.
"""

from __future__ import annotations

import numpy as np

from bench import reference

#: queries compared with the reference per run, drawn from the seed
CHECK_SAMPLE = 24
SEMIRING = "plus_times"
NUMBERS = ("ppr_band_rel",)


def job(source: int, config: dict):
    from repro.algorithms import PersonalizedPageRank
    return PersonalizedPageRank(source=int(source),
                                damping=float(config["damping"]),
                                tolerance=float(config["tolerance"]))


def reference_results(g, sources, config: dict):
    """(ref, low): the true vectors and the band's lower edge."""
    d = float(config["damping"])
    ref = reference.ppr(g, sources, d)
    low = ref - float(config["tolerance"]) * reference.ppr_shortfall(g, d)
    return ref, low


def control_results(g, sources, config: dict) -> np.ndarray:
    return reference.ppr_high(g, sources, float(config["damping"]))


def numbers(reference_out, got: np.ndarray) -> dict:
    ref, low = reference_out
    got = np.asarray(got, np.float64)
    scale = np.where(ref > 0, ref, 1.0)
    out = np.maximum(got - ref, low - got) / scale
    return {"ppr_band_rel": float(np.max(out))}
