"""The open-loop arrivals and the percentile arithmetic."""

import math

import numpy as np
import pytest

import bench_testlib  # noqa: F401  (path set-up)
from bench import arrivals, stats


def test_arrivals_fixed_count_sorted_and_seeded():
    cand = np.arange(100, 600)
    a = arrivals.open_loop(14.4, 30.0, cand, 1.0, 16,
                           np.random.default_rng(2**31 + 9))
    b = arrivals.open_loop(14.4, 30.0, cand, 1.0, 16,
                           np.random.default_rng(2**31 + 9))
    assert a == b and len(a) == round(14.4 * 30)
    due = [x.due_s for x in a]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 30.0
    assert {x.source for x in a} <= set(cand.tolist())
    c = arrivals.open_loop(14.4, 30.0, cand, 1.0, 16,
                           np.random.default_rng(1))
    assert len(c) == len(a) and c != a       # same work, another order


def test_zipf_sources_are_skewed():
    cand = np.arange(1000)
    s = arrivals.zipf_sources(cand, 20_000, 1.0, np.random.default_rng(0))
    counts = np.sort(np.bincount(s, minlength=1000))[::-1]
    # rank 1 draws ~1/H(1000) ~ 13 % of the mass, rank 10 a tenth of it
    assert 0.11 < counts[0] / len(s) < 0.16
    assert 6 < counts[0] / counts[9] < 15


def test_percentile_matches_numpy_and_sorts_failures_last():
    x = np.random.default_rng(0).random(101)
    for q in (50, 95, 99):
        assert stats.percentile(x, q) == pytest.approx(np.percentile(x, q))
    assert stats.percentile([1.0, 2.0, math.inf], 50) == 2.0
    assert stats.percentile([1.0, 2.0, math.inf], 95) == math.inf
    assert stats.percentile_summary([])["count"] == 0

