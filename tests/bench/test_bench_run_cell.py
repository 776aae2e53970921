"""`bench/run_cell.py` prints no result and exits non-zero off TPU."""

import os
import subprocess
import sys

import bench_testlib


def test_run_cell_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(bench_testlib.ROOT / "bench" / "run_cell.py"),
         "--workload", "sssp-batch64", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=bench_testlib.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
