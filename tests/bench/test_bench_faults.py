"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole run (set-up, window, check) at a tiny size on
the CPU, skipping only the harness's look for a chip, with one fault of
`bench.faults` planted in the program's session: a step that returns its
state unchanged, half of the jobs left out of the push, an answer
altered where it is read back.  (The cells run on one chip: there is no
exchange between chips to leave out.)  The same runs without a fault
come out correct, and a run with the control in the program's place
does not.  At the cells' own size these runs are made on the chip by
`bench/controls.py`."""

import json

import pytest

import bench_testlib
from bench import faults, harness

CELLS = [w["name"] for w in json.loads(
    (bench_testlib.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_testlib.tiny_root(tmp_path_factory.mktemp("faults"))


@pytest.fixture(autouse=True)
def short_drain(monkeypatch):
    monkeypatch.setattr(harness, "DRAIN_S", 1.0)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell, monkeypatch):
    out = bench_testlib.run_tiny(root, cell, monkeypatch)
    assert out["correct"], out["compared"]


@pytest.mark.parametrize("fault", [faults.unchanged, faults.half_left_out,
                                   faults.answer_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(root, cell, fault, monkeypatch):
    fault(monkeypatch.setattr)
    out = bench_testlib.run_tiny(root, cell, monkeypatch)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_run_is_not_correct(root, cell, monkeypatch):
    monkeypatch.setattr(harness, "check_device", lambda cell, root:
                        harness.load_peaks(root, "TPU v5 lite"))
    out = harness.run_cell(cell, 5, 0.5, False, root=root, control=True)
    assert out["failed"] == 0
    assert not out["correct"], out["compared"]
    assert any(c["value"] > c["limit"] for c in out["compared"].values())
