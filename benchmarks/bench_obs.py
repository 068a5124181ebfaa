"""Observability overhead benchmark (DESIGN.md §16).

Measures the wall-clock cost of the metrics + span-tracing subsystem on
the clique/host-spill cell at fusion factors T ∈ {1, 16}:

* ``observe=off`` — the default no-op path: instrumented code holding
  shared null metrics/spans.  This is the baseline every other repo
  benchmark implicitly measures, so the no-op path costing ~0% is what
  keeps BENCH trajectories comparable across PRs; a null-object
  microbenchmark quantifies it directly (ns per disabled call).
* ``observe=on`` — live registry + tracer.  Acceptance: **<3% wall-clock
  overhead**, asserted on the full-size cell (the --fast cell's per-step
  device work is small enough that scheduler noise exceeds the budget).

Every observed run is parity-asserted byte-for-byte against its
unobserved twin (observe is a pure observer — same discipline as
checkpointing, tests/test_obs.py).

A separate instrumented run with checkpointing enabled checks that the
step / wait / refill / checkpoint-commit spans are all recorded and
exports the ring's Chrome trace artifact (``artifacts/bench/obs_trace.json``
— load it at https://ui.perfetto.dev).

    PYTHONPATH=src python -m benchmarks.bench_obs [--fast]
"""
import dataclasses
import os
import tempfile
import time

import numpy as np

from repro.core.clique import make_clique_computation
from repro.core.engine import Engine, EngineConfig
from repro.data.synthetic_graphs import densifying_graph
from repro.obs import NOOP, NULL_METRIC

_T_SWEEP = (1, 16)
_OVERHEAD_BUDGET = 0.03         # acceptance: <3% wall-clock with obs on
_REQUIRED_SPANS = ("engine.step", "engine.refill", "engine.wait",
                   "checkpoint.commit")


def _timed(fn, pre=None):
    if pre is not None:
        pre()
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def _noop_micro(n: int = 200_000) -> dict:
    """ns/call for the disabled path: a null counter inc and a null span
    enter/exit, against an empty-loop control."""
    r = range(n)
    t0 = time.perf_counter()
    for _ in r:
        pass
    empty = time.perf_counter() - t0
    inc = NULL_METRIC.inc
    t0 = time.perf_counter()
    for _ in r:
        inc()
    t_inc = time.perf_counter() - t0
    span = NOOP.tracer.span
    t0 = time.perf_counter()
    for _ in r:
        with span("x"):
            pass
    t_span = time.perf_counter() - t0
    return {"noop_inc_ns": round(max(0.0, t_inc - empty) / n * 1e9, 1),
            "noop_span_ns": round(max(0.0, t_span - empty) / n * 1e9, 1)}


def run(fast: bool = False, rounds: int = 0, out_dir: str = "artifacts/bench",
        tmpdir=None):
    rounds = rounds or (5 if fast else 7)
    own_tmp = tmpdir is None
    if own_tmp:
        tmp = tempfile.TemporaryDirectory(prefix="bench_obs_")
        tmpdir = tmp.name
    try:
        # same long prioritized-run regime as bench_checkpoint: per-step
        # device work large enough that per-step host bookkeeping (what
        # observability adds) is measured against realistic step times
        n, m, batch, pool = ((192, 6000, 16, 512) if fast
                             else (256, 12000, 32, 1024))
        g = densifying_graph(n, m, seed=0)
        comp = make_clique_computation(g)
        base_cfg = EngineConfig(k=5, batch=batch, pool_capacity=pool,
                                max_steps=200_000, spill="host")
        # warm every cell's jit caches, then measure in A-B-A rounds
        # (off, on, off per T).  Overhead is the *median over rounds of
        # on / mean(surrounding offs)*: host clock/load drift — the
        # dominant noise source on shared CI hosts, an off/off control
        # pair alone wobbles ±3%, dwarfing the microseconds of
        # bookkeeping under test — is locally linear, so the symmetric
        # baseline cancels it inside each round, and the median discards
        # rounds a transient hit asymmetrically.  Best-of-N walls are
        # reported alongside for absolute numbers.
        engines = {}
        for T in _T_SWEEP:
            for mode in ("off", "on"):
                eng = Engine(comp, dataclasses.replace(
                    base_cfg, steps_per_sync=T, observe=mode == "on"))
                eng.run()                           # warm the jit caches
                engines[mode, T] = eng
        walls, results = {}, {}
        ratios = {T: [] for T in _T_SWEEP}
        for _ in range(rounds):
            for T in _T_SWEEP:
                a, results["off", T] = _timed(engines["off", T].run)
                b, results["on", T] = _timed(engines["on", T].run)
                c, _ = _timed(engines["off", T].run)
                walls["off", T] = min(walls.get(("off", T), a), a, c)
                walls["on", T] = min(walls.get(("on", T), b), b)
                ratios[T].append(b / ((a + c) / 2))

        rows = []
        for T in _T_SWEEP:
            base_res, obs_res = results["off", T], results["on", T]
            # pure observer: observed runs change nothing
            assert np.array_equal(base_res.result_keys,
                                  obs_res.result_keys), \
                f"T={T}: result keys diverged under observe"
            assert np.array_equal(base_res.result_states,
                                  obs_res.result_states), \
                f"T={T}: result states diverged under observe"
            overhead = float(np.median(ratios[T])) - 1.0
            eng = engines["on", T]
            assert eng.obs.metrics.get(
                "engine_steps_total").value > 0, "observer recorded nothing"
            for mode in ("off", "on"):
                rows.append(dict(
                    workload="clique", spill="host", T=T, observe=mode,
                    wall_s=round(walls[mode, T], 4),
                    steps=results[mode, T].steps,
                    overhead_pct=round(100 * overhead, 2)
                    if mode == "on" else 0.0))
            if not fast:
                assert overhead < _OVERHEAD_BUDGET, \
                    f"T={T}: observe-on overhead {100 * overhead:.2f}% " \
                    f"exceeds the {100 * _OVERHEAD_BUDGET:.0f}% budget"

        micro = _noop_micro()
        # the disabled path must stay in the tens-of-nanoseconds regime —
        # the "~0% when off" half of the §16 budget
        assert micro["noop_inc_ns"] < 1000 and micro["noop_span_ns"] < 2000
        rows.append(dict(workload="noop-micro", **micro))

        # ---- trace-attribution run: observe + checkpointing, exported
        ck_eng = Engine(comp, dataclasses.replace(
            base_cfg, steps_per_sync=16, observe=True, checkpoint_every=64,
            checkpoint_dir=os.path.join(tmpdir, "ckpt")))
        ck_eng.run()                                # warm
        ck_eng.obs.tracer.clear()
        wall, res = _timed(ck_eng.run)
        assert res.refilled > 0, "cell too small: refill phase never ran"
        spans = ck_eng.obs.tracer.spans()
        names = {s[0] for s in spans}
        missing = [s for s in _REQUIRED_SPANS if s not in names]
        assert not missing, f"required phases absent from trace: {missing}"
        os.makedirs(out_dir, exist_ok=True)
        trace_path = ck_eng.obs.tracer.export_chrome_trace(
            os.path.join(out_dir, "obs_trace.json"))
        print(f"Chrome trace written to {trace_path} "
              f"(load at https://ui.perfetto.dev)")
        rows.append(dict(
            workload="trace", spans_recorded=len(spans),
            wall_s=round(wall, 4), trace_path=trace_path))
        return rows
    finally:
        if own_tmp:
            tmp.cleanup()


def main(fast: bool = False):
    rows = run(fast=fast)
    print("\n(top-k parity asserted on every observed row; <3% overhead"
          " asserted full-size)")
    print(f"{'workload':>10} {'T':>3} {'observe':>8} {'steps':>6} "
          f"{'wall s':>8} {'overhead':>9}")
    for r in rows:
        if r["workload"] != "clique":
            continue
        print(f"{r['workload']:>10} {r['T']:>3} {r['observe']:>8} "
              f"{r['steps']:>6} {r['wall_s']:>8.3f} "
              f"{r['overhead_pct']:>8.2f}%")
    micro = next(r for r in rows if r["workload"] == "noop-micro")
    print(f"disabled-path cost: {micro['noop_inc_ns']}ns/inc, "
          f"{micro['noop_span_ns']}ns/span")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    main(fast=ap.parse_args().fast)
