#!/usr/bin/env python3
"""The control of the benchmark's check: a run that breaks the
configuration's guarantee of exact answers, and must read not correct.

The control is the program's own anytime path: every query of the window
carries ``step_budget`` = :data:`CONTROL_STEPS`, so the engine stops each
search early and returns the best results found so far, the shortcut a
change that caps search work would take.  It runs the cell as
``run.py`` does, at the cell's own sizes and load, once per seed, all in
one process, and prints each seed's compared numbers:

    python3 bench/control.py --workload mico-wclique.c4 --seconds 15 \\
        --seeds 11 12 13

The benchmark's own runs never run it; ``test_bench_control.py`` runs it
at a size the tests can hold.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL_STEPS = 8


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [ROOT]
    from bench import run
    run.configure_jax()
    from bench import harness
    cell = harness.resolve(harness.load_benchmark(ROOT), args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        try:
            res = harness.run_cell(cell, seed, args.seconds, False, t0,
                                   overrides=dict(step_budget=CONTROL_STEPS))
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps(dict(seed=seed, correct=res["correct"],
                              attempted=res["attempted"],
                              limits=res["limits"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
