#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

  python3 bench/run_cell.py --workload sssp-batch64 --seed 7 \
      --seconds 30 --trace 0

Run from the root of a checkout holding `BENCHMARK.json`, `bench/` and
the program under `src/`.  Set-up (graph, session, warm-up of every
program the window calls) is timed as `setup_s`; then the window runs
for `--seconds`; then what the window produced is compared with the
plain references.  `--trace 0` prints the cell's end-to-end metrics,
`--trace 1` traces the window with the JAX profiler and prints its
per-layer metrics.  The numbers compared, each beside its limit, are the
last lines on stderr and the last key of the result line, which is the
last line on stdout.

The run exits non-zero without a result off TPU, with fewer chips than
the cell asks for, or on a device kind missing from `bench/peaks.json`.
JAX's persistent compilation cache is kept at `<checkout>/.jax_cache`.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the package imports as `bench.*`; the program from src/
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import jax
    # every program the set-up compiles, small ones too, is kept, so that
    # a later run of the cell finds them all in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), root=ROOT, t_start=T_START)
    except harness.ChipMissing as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    for name, c in out["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
