"""Cells load from data: every name in `BENCHMARK.json` resolves to its
files, the file keeps to the benchmark's contract, and a cell added as
new files alone runs."""

import json
import re

import pytest

import bench_testlib
from bench import harness

SPEC = json.loads((bench_testlib.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.load_cell(cell)
    root = bench_testlib.ROOT
    assert harness.load_module(root, "drivers", c.traffic["kind"]).Driver
    alg = harness.load_module(root, "algorithms", c.config["algorithm"])
    assert set(c.config["limits"]) == set(alg.NUMBERS)
    names = [m["name"] for m in c.end_to_end + c.per_layer]
    assert "setup_s" in names and len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.load_metric(root, m["name"]).read)
    for m in c.per_layer:
        assert m["moves"] in [e["name"] for e in c.end_to_end]


def test_names_and_keys_keep_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for entry in SPEC["configs"] + SPEC["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cfgs = {c["name"] for c in SPEC["configs"]}
    assert {w["config"] for w in SPEC["workloads"]} == cfgs
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert all(w["chips"] == 1 for w in SPEC["workloads"])
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        assert not set(c["reduced"]) & {"edge_factor", "block_size"}


def test_a_cell_added_as_new_files_runs(tmp_path, monkeypatch):
    root = bench_testlib.tiny_root(tmp_path)
    # a new traffic mix, a new metric reader and a new cell: files and
    # entries only, no edit to any file the benchmark has
    (root / "bench" / "traffic" / "closed-batch4.json").write_text(
        json.dumps({"kind": "closed_batch", "batch": 4}))
    (root / "bench" / "metrics" / "batches_in_window.py").write_text(
        "def read(run):\n    return len(run.recorder.batches)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "sssp-batch4", "config": "kron14-sssp",
                              "traffic": "closed-batch4", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({
        "name": "batches_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "policy",
        "moves": "queries_per_s", "workloads": ["sssp-batch4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = bench_testlib.run_tiny(root, "sssp-batch4", monkeypatch)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 4
    assert set(out["metrics"]) == {"queries_per_s", "setup_s"}
    assert list(out)[-1] == "compared"
    run = out["compared"]
    assert run["reach_mismatch"]["value"] == 0


def test_unknown_cell_and_unknown_device_kind_are_errors():
    with pytest.raises(ValueError, match="no workload"):
        harness.load_cell("no-such-cell")
    with pytest.raises(harness.ChipMissing, match="peaks.json"):
        harness.load_peaks(bench_testlib.ROOT, "TPU v9 imaginary")
    assert harness.load_peaks(bench_testlib.ROOT,
                              "TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_split_metrics_share_their_base_reader():
    root = bench_testlib.ROOT
    assert not (root / "bench" / "metrics"
                / "device_idle_share.serve.py").exists()
    mod = harness.load_metric(root, "device_idle_share.serve")
    assert mod.__file__.endswith("device_idle_share.py")
    with pytest.raises(FileNotFoundError):
        harness.load_metric(root, "no_such_metric.serve")


@pytest.mark.parametrize("key", ["undirected", "permuted"])
def test_a_graph_the_generator_cannot_make_is_refused(key):
    cfg = harness.load_cell(CELLS[0]).config
    cfg[key] = False
    with pytest.raises(ValueError, match=key):
        harness.make_graph(cfg, None)
