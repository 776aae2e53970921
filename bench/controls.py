#!/usr/bin/env python3
"""Whole runs of a cell at its own size in which the comparison has to
come out not correct: the control, or a planted fault.

  python3 bench/controls.py --workload sssp-batch64 --seeds 1 2 3 \
      --seconds 10 [--fault answer_altered] [--drain 10]

Without `--fault`, each run is the cell's own (set-up, window at the
cell's load, check), with the algorithm's control (its reference one
precision down, `bench/algorithms/<alg>.py`: `control_results`) put in
the program's place in `bench.harness.compare`.  With `--fault <name>`,
the program runs with that fault of `bench.faults` planted under the
timed path.  Every run prints one JSON line: `correct`, `failed` and each
number compared beside its limit.  The script exits 1 if a control run
passed every limit, or a fault run came out correct.  All runs share one process, so it holds the chip
throughout; the benchmark's own runs never run this.
"""

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--drain", type=float, default=None,
                    help="seconds past the window a request may still "
                         "complete (default: the harness's)")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import faults, harness
    if args.drain is not None:
        harness.DRAIN_S = args.drain
    if args.fault is not None:
        faults.FAULTS[args.fault](setattr)
    caught = True
    for seed in args.seeds:
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               root=ROOT, control=args.fault is None)
        if args.fault is None:       # the control has to fail a number
            caught &= any(c["value"] is None or c["value"] > c["limit"]
                          for c in out["compared"].values())
        else:
            caught &= not out["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "mode": args.fault or "control",
                          "device": out["device"]["kind"],
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "compared": out["compared"]}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
