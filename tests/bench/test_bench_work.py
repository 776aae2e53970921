"""The kernel's work and bytes, and the roofline, against hand counts."""

import pytest

import bench_testlib  # noqa: F401  (path set-up)
from bench import work

V5E = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}


def test_min_plus_bytes_by_hand():
    # 10 selected pairs of 64x64 f32 tiles, 2 supersteps, 8 jobs over 4
    # blocks: 5 state arrays of 8*4*64 f32 per superstep
    tiles = 10 * 64 * 64 * 4
    state = 2 * 5 * 8 * 4 * 64 * 4
    assert work.kernel_bytes(10, 2, jobs=8, num_blocks=4, block=64,
                             semiring="min_plus") == tiles + state
    assert work.kernel_flops(10, jobs=8, block=64,
                             semiring="min_plus") == 0


def test_plus_times_bytes_and_flops_by_hand():
    assert work.kernel_bytes(3, 1, jobs=64, num_blocks=2, block=8,
                             semiring="plus_times") == (
        3 * 8 * 8 * 4 + 3 * 64 * 2 * 8 * 4)
    # [64, 8] @ [8, 8] per pair: 2 * 64 * 8 * 8 flops
    assert work.kernel_flops(3, jobs=64, block=8,
                             semiring="plus_times") == 3 * 2 * 64 * 8 * 8


@pytest.mark.parametrize("nbytes,flops,bound", [
    (819e9, 1.0, "bytes"), (1.0, 197e12, "flops"), (819e9, 2 * 197e12,
                                                    "flops")])
def test_roofline_takes_the_larger_bound(nbytes, flops, bound):
    least, which = work.roofline_seconds(nbytes, flops, V5E)
    assert which == bound
    assert least == pytest.approx(max(nbytes / 819e9, flops / 197e12))


def test_kernel_seconds_matches_only_the_kernel():
    ops = {"_fused_jit": 1.5, "fusion": 9.0, "while": 0.5, "copy": 1.0}
    assert work.kernel_seconds(ops) == pytest.approx(1.5)
