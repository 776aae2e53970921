"""The trace reduction: device busy union, idle share, per-op time and
idle gaps charged to host spans."""

import types

import pytest

import bench_testlib
from bench import work, xplane


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs]) for ln, evs in lines])


def test_reduce_synthetic_planes():
    host = _plane("/host:CPU", [("python", [
        ("bench.window", 1000, 10_000), ("bench.run", 1000, 5000),
        ("bench.read_back", 6000, 5000), ("other", 0, 100)])])
    dev = _plane("/device:TPU:0", [
        ("XLA Ops", [
            ("%_fused_jit.1 = (f32[8]) custom-call(...)", 500, 1500),
            ("%while.3 = (s32[]) while(...)", 3000, 4000),
            ("%_fused_jit.2 = (f32[8]) custom-call(...)", 3500, 1000),
            ("%copy.1 = f32[8] copy(...)", 12_000, 500)]),
        ("XLA Modules", [("jit_step_fn(1)", 0, 20_000)])])
    r = xplane.reduce_planes([host, dev])
    assert r["window_s"] == pytest.approx(10_000e-9)
    # busy: [1000, 2000) + [3000, 7000); the first op is clipped
    assert r["busy_s"] == pytest.approx(5000e-9)
    # self time: the while less the kernel nested in it
    assert r["ops"]["_fused_jit"] == pytest.approx(2000e-9)
    assert r["ops"]["while"] == pytest.approx(3000e-9)
    assert "copy" not in r["ops"]                 # outside the window
    # gaps: [2000, 3000) under bench.run, [7000, 11000) under read_back
    assert r["idle_gaps"]["bench.run"] == pytest.approx(1000e-9)
    assert r["idle_gaps"]["bench.read_back"] == pytest.approx(4000e-9)


def test_busy_is_averaged_over_devices():
    host = _plane("/host:CPU", [("t", [("bench.window", 0, 1000)])])
    d0 = _plane("/device:TPU:0", [("XLA Ops", [("a", 0, 1000)])])
    d1 = _plane("/device:TPU:1", [("XLA Ops", [("a", 0, 500)])])
    r = xplane.reduce_planes([host, d0, d1])
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(750e-9)


def test_missing_window_or_device_is_an_error():
    dev = _plane("/device:TPU:0", [("XLA Ops", [("a", 0, 10)])])
    with pytest.raises(ValueError, match="bench.window"):
        xplane.reduce_planes([dev])
    host = _plane("/host:CPU", [("t", [("bench.window", 0, 1000)])])
    with pytest.raises(ValueError, match="TPU"):
        xplane.reduce_planes([host])


def test_reduce_recorded_trace():
    """A trace recorded on a TPU v5e: the `sssp-batch8` cell's path at
    scale 10 (batches of 8 SSSP jobs, Pallas kernel), 0.2 s window."""
    path = (bench_testlib.ROOT / "bench" / "testdata"
            / "sssp-batch8-scale10.xplane.pb.gz")
    r = xplane.reduce_file(str(path))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.200152828)
    assert r["busy_s"] == pytest.approx(0.001387054)
    assert work.kernel_seconds(r["ops"]) == pytest.approx(0.000796536)
    top = xplane.top(r["ops"], 3)
    assert top[0][0] == "_fused_jit" and len(top) == 3
    assert set(r["idle_gaps"]) <= {"bench.submit", "bench.run",
                                   "bench.read_back", "(none)"}
    assert sum(r["idle_gaps"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
