"""SSSP queries (Graph500 kernel 3): the program's job, the reference, the
control and the numbers compared.

  reach_mismatch  vertices reached on one side only; exact, limit 0.
  dist_rel_gap    the widest |got - ref| / ref over vertices both reach
                  (ref 0, at the root, compares absolutely): float32 sums
                  along a path of a few hops round within ~1e-6 of the
                  float64 Dijkstra; bfloat16 distances are off by ~1e-2.
"""

from __future__ import annotations

import numpy as np

from bench import reference

#: queries compared with the reference per run, drawn from the seed
CHECK_SAMPLE = 64
SEMIRING = "min_plus"
NUMBERS = ("reach_mismatch", "dist_rel_gap")


def job(source: int, config: dict):
    from repro.algorithms import SSSP
    return SSSP(source=int(source))


def reference_results(g, sources, config: dict) -> np.ndarray:
    return reference.sssp(g, sources)


def control_results(g, sources, config: dict) -> np.ndarray:
    return reference.sssp_bf16(g, sources)


def numbers(ref: np.ndarray, got: np.ndarray) -> dict:
    got = np.asarray(got, np.float64)
    fin_r, fin_g = np.isfinite(ref), np.isfinite(got)
    both = fin_r & fin_g
    gap = (np.abs(got[both] - ref[both])
           / np.where(ref[both] > 0, ref[both], 1.0))
    return {"reach_mismatch": float(np.sum(fin_r != fin_g)),
            "dist_rel_gap": float(gap.max()) if gap.size else 0.0}
