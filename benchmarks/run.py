"""Benchmark harness: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

``--json PATH`` writes this run's per-benchmark timings + result rows to
PATH (the per-PR artifact CI uploads) AND appends the run's numeric cells
to the cumulative ``BENCH_TRAJECTORY.jsonl`` — one
``{"pr", "benchmark", "cell", "value"}`` row per measurement, deduped by
(pr, benchmark, cell) with newest-wins, so the perf trajectory across
PRs lives in one greppable file.  ``--backfill F.json [G.json ...]``
ingests existing per-PR artifacts into the trajectory without running
anything.
"""
import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import bench_checkpoint, bench_clique, bench_distributed, \
    bench_engine, bench_iso, bench_k, bench_labeled, bench_obs, \
    bench_pattern, bench_service, bench_vpq  # noqa: E402

REGISTRY = [("clique (Fig 9-11)", bench_clique),
            ("pattern (Fig 12-14)", bench_pattern),
            ("iso (Fig 15-17)", bench_iso),
            ("k-sweep (Fig 18)", bench_k),
            ("vpq (Fig 19)", bench_vpq),
            ("service (§9)", bench_service),
            ("distributed (§11)", bench_distributed),
            ("labeled (§12)", bench_labeled),
            ("engine macro-step (§13)", bench_engine),
            ("checkpoint (§15)", bench_checkpoint),
            ("observability (§16)", bench_obs)]

# keys that *identify* a result row rather than measure it — they name
# the trajectory cell so the same configuration is comparable across PRs
ID_KEYS = ("workload", "spill", "checkpoint_every", "observe", "T",
           "shards", "sync_every", "devices", "n", "m", "k", "clusters",
           "steps_per_sync", "skew", "every", "kernel", "mode", "graph")


def _cells(obj, prefix=""):
    """Flatten a benchmark's result structure (list-of-row-dicts, nested
    dicts, or any mix) into ``(cell, value)`` pairs over numeric leaves."""
    if isinstance(obj, dict):
        ident = ",".join(f"{k}={obj[k]}" for k in ID_KEYS if k in obj)
        base = f"{prefix}{ident}:" if ident else prefix
        for k, v in obj.items():
            if k in ID_KEYS:
                continue
            if isinstance(v, bool):
                continue
            if isinstance(v, (int, float)):
                yield f"{base}{k}", v
            elif isinstance(v, (dict, list)):
                yield from _cells(v, prefix=f"{base}{k}.")
    elif isinstance(obj, list):
        for i, item in enumerate(obj):
            if isinstance(item, dict) and any(k in item for k in ID_KEYS):
                yield from _cells(item, prefix=prefix)   # self-identifying
            elif isinstance(item, (dict, list)):
                yield from _cells(item, prefix=f"{prefix}{i}.")


def trajectory_rows(pr: str, benchmarks: dict) -> list:
    """``{pr, benchmark, cell, value}`` rows from a per-PR artifact's
    ``benchmarks`` mapping (name -> {seconds, results})."""
    rows = []
    for name, entry in benchmarks.items():
        rows.append({"pr": pr, "benchmark": name, "cell": "seconds",
                     "value": entry["seconds"]})
        for cell, value in _cells(entry.get("results")):
            rows.append({"pr": pr, "benchmark": name, "cell": cell,
                         "value": value})
    return rows


def append_trajectory(path: str, rows: list) -> int:
    """Merge ``rows`` into the cumulative JSONL, deduped by
    (pr, benchmark, cell) — a re-run of the same PR's sweep replaces its
    old rows in place.  Returns the file's row count after the merge."""
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    r = json.loads(line)
                    merged[r["pr"], r["benchmark"], r["cell"]] = r
    for r in rows:
        merged[r["pr"], r["benchmark"], r["cell"]] = r
    ordered = sorted(merged.values(),
                     key=lambda r: (r["pr"], r["benchmark"], r["cell"]))
    with open(path, "w") as f:
        for r in ordered:
            f.write(json.dumps(r) + "\n")
    return len(ordered)


def _pr_label(json_path: str) -> str:
    m = re.search(r"PR(\d+)", os.path.basename(json_path))
    return f"PR{m.group(1)}" if m else "dev"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out", default="artifacts/bench")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write per-benchmark wall-clock timings + "
                         "result rows to PATH (e.g. BENCH_PR8.json) — the "
                         "perf-trajectory artifact CI uploads; its cells "
                         "are appended to --trajectory too")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only benchmarks whose registry name contains "
                         "SUBSTR (e.g. 'distributed' for the stale-bound "
                         "K-sweep artifact)")
    ap.add_argument("--trajectory", default="BENCH_TRAJECTORY.jsonl",
                    metavar="PATH",
                    help="cumulative cross-PR trajectory JSONL "
                         "(set empty to skip)")
    ap.add_argument("--pr", default=None, metavar="LABEL",
                    help="trajectory PR label (default: PR<N> parsed from "
                         "the --json filename, else 'dev')")
    ap.add_argument("--backfill", nargs="+", default=None, metavar="JSON",
                    help="ingest existing per-PR artifacts (BENCH_PR*.json) "
                         "into --trajectory and exit without benchmarking")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.backfill:
        rows = []
        for path in args.backfill:
            with open(path) as f:
                doc = json.load(f)
            rows += trajectory_rows(args.pr or _pr_label(path),
                                    doc["benchmarks"])
        total = append_trajectory(args.trajectory, rows)
        print(f"backfilled {len(rows)} rows from {len(args.backfill)} "
              f"artifact(s); {args.trajectory} now has {total} rows")
        return

    os.makedirs(args.out, exist_ok=True)
    results = {}
    timings = {}
    for name, mod in REGISTRY:
        if args.only and args.only not in name:
            continue
        print(f"\n=== {name} ===")
        t0 = time.time()
        results[name] = mod.main(fast=args.fast)
        timings[name] = round(time.time() - t0, 3)
        print(f"[{name}] {timings[name]:.1f}s")
    with open(os.path.join(args.out, "results.json"), "w") as f:
        json.dump(results, f, indent=1, default=str)
    if args.json:
        benchmarks = {name: {"seconds": timings[name],
                             "results": results[name]}
                      for name in results}
        with open(args.json, "w") as f:
            json.dump({"fast": args.fast,
                       "total_seconds": round(sum(timings.values()), 3),
                       "benchmarks": benchmarks},
                      f, indent=1, default=str)
        print(f"per-benchmark timings written to {args.json}")
        if args.trajectory:
            rows = trajectory_rows(args.pr or _pr_label(args.json),
                                   benchmarks)
            total = append_trajectory(args.trajectory, rows)
            print(f"{len(rows)} trajectory rows appended to "
                  f"{args.trajectory} ({total} total)")
    print("\nbenchmarks complete.")


if __name__ == "__main__":
    main()
