"""Shared helpers of the benchmark's CPU tests: the repository root on
sys.path (the benchmark imports as `bench`, the program from src/) and a
copy of the benchmark at a tiny size that a CPU run can hold."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def tiny_root(tmp_path: pathlib.Path, *, scale: int = 9, batch: int = 8,
              rate: float = 32.0) -> pathlib.Path:
    """`BENCHMARK.json` and `bench/` copied under `tmp_path`, every
    configuration cut to `scale` on the jnp push (the Pallas kernel would
    run interpreted), every traffic mix to `batch` jobs at once."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    for path in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["scale"] = scale
        cfg["engine"]["use_pallas"] = False
        path.write_text(json.dumps(cfg))
    for path in (root / "bench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        if t["kind"] == "closed_batch":
            t["batch"] = batch
        else:
            t["max_running"] = batch
            t["rate"] = rate
        path.write_text(json.dumps(t))
    return root


def run_tiny(root: pathlib.Path, cell: str, monkeypatch, *, seed: int = 5,
             seconds: float = 0.5) -> dict:
    """A whole `--trace 0` run on the CPU: only the harness's look for a
    chip is skipped (the peaks are the v5e's)."""
    from bench import harness
    monkeypatch.setattr(harness, "check_device", lambda cell, root:
                        harness.load_peaks(root, "TPU v5 lite"))
    return harness.run_cell(cell, seed, seconds, False, root=root)
