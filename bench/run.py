#!/usr/bin/env python3
"""Run one cell of the discovery service's benchmark on the chip.

    python3 bench/run.py --workload mico-wclique.c4 --seed 7 --seconds 10 \\
        --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout.  With ``--trace 0`` the last line of standard output holds
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
(from a profiler trace of the window).  Earlier lines say how set-up went,
how many programs were compiled inside the window, and how the answers
were checked.  The last lines of standard error give each number the
check compared, beside its limit.

A run that finds no TPU, or fewer chips than the cell asks for, prints no
result and exits with 2.

Adding to the benchmark takes files and entries only (``README.md``
here): a configuration is ``configs/<name>.json``, a traffic mix
``traffic/<name>.json``, a query family ``families/<name>.py``, a
per-layer metric ``metrics/<name>.py`` (a ``read(ctx)`` that returns a
number or None), and a cell an entry of ``workloads``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def configure_jax():
    """Paths and JAX settings of every benchmark process; call before JAX
    touches a device."""
    scratch = os.path.join(ROOT, ".bench_cache")
    os.makedirs(os.path.join(scratch, "tpu_logs"), exist_ok=True)
    # the TPU library logs under the system temp directory unless told
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(scratch, "tpu_logs"))
    # the package is imported as ``bench``, never module by module from
    # its own directory
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    # one fixed cache directory in the checkout, so that only a cell's
    # first run there compiles; every program is kept, however quick to
    # compile, and kernels carry no source paths into their cache keys
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(scratch, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_traceback_in_locations_limit", 0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    configure_jax()
    from bench import harness

    cell = harness.resolve(harness.load_benchmark(ROOT), args.workload)

    def emit(obj):
        print(json.dumps(obj), flush=True)

    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START, emit=emit)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, v in result["limits"].items():
        print(f"{name} {v['value']} (limit {v['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    if result["limits"]["unanswered"]["value"]:
        # a query that never returned holds its thread: leave without it
        sys.stderr.flush()
        os._exit(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
