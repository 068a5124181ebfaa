"""Run one cell of the benchmark: set up, measure a window, check answers.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration, whose file holds the deployment (graph sizes, request
fields, guarantees), and a traffic mix under ``traffic/``.  The harness:

1. generates the graph from the seed on the host and registers it with a
   ``DiscoveryService``, the object the JSONL server drives;
2. warms every program the window uses with queries of the cell's own
   shapes that do not write the result cache (set-up ends here);
3. drives ``DiscoveryService.serve`` with the mix for the window: the
   closed loop stops admitting at the deadline and the window closes when
   the batch in flight returns, so every query sent is answered inside it;
4. reads the device's peak memory, then checks every answer of the
   window against the plain reference of its query family
   (``families/<family>.py``);
5. with ``trace`` on, records a profiler trace of the window and reads the
   cell's per-layer metrics with the readers under ``metrics/``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import statistics
import threading
import time
from typing import Callable, Dict, List, Optional

from . import devtrace, roofline
from .graphs import make_graph
from .reference import Graph
from .traffic import Traffic, load as load_traffic

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# everything the harness writes at run time lives here (gitignored)
SCRATCH = os.path.join(ROOT, ".bench_cache")
WINDOW_SPAN = "bench.window"
GRACE_S = 60.0


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ specs
def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell ``workload`` with its configuration, traffic and metrics,
    each found by its name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, config["file"])) as f:
        cfg = json.load(f)

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return dict(
        name=workload, chips=int(cell["chips"]), config=cfg,
        config_name=config["name"],
        traffic=load_traffic(os.path.join(
            BENCH_DIR, "traffic", cell["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---------------------------------------------------------------- counting
class CompileCounter:
    """Programs the backend was asked for, and how many of them the
    persistent cache answered; the difference compiled."""

    def __init__(self, jax):
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.loads = self.hits = 0
        self.seconds = 0.0

        def on_duration(event, duration, **_kw):
            if event == BACKEND_COMPILE_EVENT:
                self.loads += 1
                self.seconds += duration

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        self._jax = jax
        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def close(self) -> None:
        on_duration, on_event = self._listeners
        self._jax.monitoring.unregister_event_duration_listener(on_duration)
        self._jax.monitoring.unregister_event_listener(on_event)

    def reading(self) -> dict:
        return dict(loads=self.loads, cache_hits=self.hits,
                    compiles=self.loads - self.hits,
                    seconds=self.seconds)


def counters(obs) -> Dict[str, float]:
    if obs is None:
        return {}
    return {name: m["value"] for name, m in obs.metrics.snapshot().items()
            if m["type"] == "counter"}


# ------------------------------------------------------------------ window
class Window:
    """Drives the service with the mix; runs in a thread of its own so
    that a query that never returns cannot hold the run past its grace."""

    def __init__(self, svc, traffic: Traffic, make_request, seconds: float,
                 jax):
        self.svc = svc
        self.traffic = traffic
        self.make_request = make_request
        self.seconds = seconds
        self.jax = jax
        self.records: List[dict] = []
        self.in_flight: List[dict] = []
        self.t0 = self.t_close = None
        self.done = threading.Event()
        self.error: Optional[BaseException] = None

    def start(self) -> None:
        threading.Thread(target=self._run, daemon=True,
                         name="bench-window").start()

    def _run(self) -> None:
        try:
            with self.jax.profiler.TraceAnnotation(WINDOW_SPAN):
                self.t0 = time.perf_counter()
                self._closed()
                self.t_close = time.perf_counter()
        except BaseException as e:     # reported by the caller
            self.error = e
        finally:
            self.done.set()

    def _serve(self, batch: List[dict]) -> None:
        """``batch``: records with ``i``, ``query`` and ``sent``."""
        ann = self.jax.profiler.TraceAnnotation
        with ann("bench.generate"):
            reqs = [self.make_request(r["query"]) for r in batch]
        self.in_flight = batch
        with ann("bench.serve"):
            resps = self.svc.serve(reqs)
        t_ret = time.perf_counter()
        for r, resp in zip(batch, resps):
            r.update(returned=t_ret, latency_s=t_ret - r["sent"],
                     status=resp.status, error=resp.error,
                     terminated=resp.terminated, cached=resp.cached,
                     result_keys=resp.result_keys, results=resp.results,
                     stats=resp.stats)
            self.records.append(r)
        self.in_flight = []

    def _closed(self) -> None:
        deadline = self.t0 + self.seconds
        clients = self.traffic.batch_size
        i = 0
        while time.perf_counter() < deadline:
            with self.jax.profiler.TraceAnnotation("bench.generate"):
                qs = [self.traffic.query(i + c) for c in range(clients)]
            now = time.perf_counter()
            self._serve([dict(i=i + c, query=q, sent=now)
                         for c, q in enumerate(qs)])
            i += clients


def engine_totals(records: List[dict]) -> dict:
    """The window's executed queries and what their searches did, summed
    from each response's ``stats``: super-steps, entries spilled to the
    VPQ, refilled from it into the device pool, and dropped there as
    dominated (late pruning)."""
    ran = [r["stats"] for r in records
           if r["status"] == "ok" and not r["cached"]]
    out = dict(executed=len(ran))
    for key in ("steps", "spilled", "refilled", "late_pruned"):
        out[key] = sum(st.get(key, 0) for st in ran)
    return out


# ------------------------------------------------------------------- check
def check(records: List[dict], unanswered: int, graph: Graph,
          traffic: Traffic) -> dict:
    """Every answered query of the window against the reference: answers
    that differ (``mismatched``), answers the service marks as cut short
    (``incomplete``), error responses (``failed``) and queries that never
    returned (``unanswered``)."""
    out = dict(mismatched=0, incomplete=0, failed=0, unanswered=unanswered)
    for r in records:
        if r["status"] != "ok":
            out["failed"] += 1
            continue
        out["incomplete"] += r["terminated"] != "complete"
        keys, results = traffic.reference(graph, r["query"])
        out["mismatched"] += (r["result_keys"] != keys or
                              r["results"] != results)
    return out


# every comparison is exact
LIMITS = dict(mismatched=0, incomplete=0, failed=0, unanswered=0)


# --------------------------------------------------------------------- run
def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             grace_s: float = GRACE_S, overrides: Optional[dict] = None,
             emit: Callable[[dict], None] = lambda d: None) -> dict:
    """One run of ``cell``; returns the result line's object.  ``t_start``
    is when the process started (the origin of ``setup_s``).
    ``overrides`` are request fields set on the window's queries; only
    the control (``control.py``) sets any."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and (platform != "tpu" or len(devices) < cell["chips"]):
        raise NoChip(f"the cell needs {cell['chips']} TPU chip(s); JAX "
                     f"sees {len(devices)} {platform} device(s)")
    used = devices[:cell["chips"]]
    device = dict(platform=platform, kind=devices[0].device_kind,
                  count=len(devices))
    peaks = roofline.peaks_for(device["kind"]) if require_tpu else None
    compiles = CompileCounter(jax)
    try:
        return _measure(jax, cell, seed, seconds, trace, t_start, used,
                        device, peaks, compiles, grace_s, overrides, emit)
    finally:
        compiles.close()


def _measure(jax, cell, seed, seconds, trace, t_start, used, device, peaks,
             compiles, grace_s, overrides, emit) -> dict:
    from repro.core.graph import GraphStore
    from repro.obs import Observability
    from repro.service import (DiscoveryRequest, DiscoveryService,
                               GraphRegistry)

    cfg = cell["config"]
    handle = cell["config_name"]
    n, edges, labels = make_graph(cfg, seed)
    registry = GraphRegistry()
    registry.register(handle, GraphStore.from_edges(n, edges, labels=labels))
    obs = Observability() if trace else None
    svc = DiscoveryService(registry=registry, observability=obs)
    traffic = Traffic(cell["traffic"], n, labels, seed)
    fields = dict(cfg.get("request", {}), graph=handle)
    if trace:
        fields["observe"] = True

    def make_request(query: dict, **extra):
        return DiscoveryRequest(**dict(fields, **query, **extra))

    # a warm-up query runs its engine's start and one step: every program
    # a query of its shape runs, without the rest of the search; it does
    # not touch the result cache
    warm = traffic.warmup()
    size = traffic.batch_size
    for b in range(0, len(warm), size):
        for resp in svc.serve([make_request(q, use_cache=False,
                                            step_budget=1)
                               for q in warm[b:b + size]]):
            if resp.status != "ok":
                raise RuntimeError(f"warm-up query failed: {resp.error}")
    setup_s = time.perf_counter() - t_start
    emit(dict(setup=dict(setup_s=setup_s, n=n, edges=len(edges),
                         warmup_queries=len(warm),
                         compiles=compiles.reading())))

    before = counters(obs)
    c0 = compiles.reading()
    trace_dir = os.path.join(SCRATCH, "trace", cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # no Python tracer: host events are the runtime's trace points
        # and the harness's annotations
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    window = Window(svc, traffic,
                    lambda q: make_request(q, **(overrides or {})),
                    seconds, jax)
    window.start()
    finished = window.done.wait(seconds + grace_s)
    if trace:
        jax.profiler.stop_trace()
    if window.error is not None:
        raise window.error
    c1 = compiles.reading()
    emit(dict(window=dict(
        finished=finished,
        compiles_in_window={k: c1[k] - c0[k] for k in c1},
        engine=engine_totals(window.records))))
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used)
    after = counters(obs)

    records = list(window.records)
    unanswered = 0 if finished else len(window.in_flight)
    t_end = window.t_close if finished else time.perf_counter()
    window_s = t_end - window.t0
    t_check = time.perf_counter()
    verdict = check(records, unanswered, Graph(n, edges, labels), traffic)
    emit(dict(check=dict(verdict, checked=len(records),
                         seconds=time.perf_counter() - t_check)))
    correct = bool(records) and all(verdict[k] <= LIMITS[k]
                                    for k in LIMITS)

    result = dict(correct=correct,
                  attempted=len(records) + unanswered,
                  failed=verdict["failed"] + unanswered,
                  metrics={}, device=dict(device,
                                          memory_peak_bytes=memory_peak))
    lat = [r["latency_s"] for r in records]
    if not trace:
        values = dict(
            setup_s=setup_s,
            queries_per_s=len(records) / window_s,
            latency_p50_ms=1e3 * statistics.median(lat) if lat else None)
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = dict(
                    value=values[m["name"]], unit=m["unit"])
    elif finished:
        events = devtrace.load_events(devtrace.find_xplane(trace_dir))
        t0, t1 = devtrace.window_of(events, WINDOW_SPAN)
        reduced = devtrace.reduce_events(events, t0, t1,
                                         exclude_host=(WINDOW_SPAN,))
        if reduced is None:
            raise RuntimeError("the trace holds no device operation in the "
                               "window")
        result["device"].update(busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
        result["breakdown"] = dict(device_ops=reduced["device_ops"][:10],
                                   idle_gaps=reduced["idle_gaps"][:10])
        emit(dict(trace=dict(busy_by_device=reduced["busy_by_device"],
                             device_ops=reduced["device_ops"][:25],
                             idle_gaps=reduced["idle_gaps"][:25])))
        ctx = dict(cell=cell, config=cfg, records=records,
                   counters={k: after.get(k, 0) - before.get(k, 0)
                             for k in after},
                   trace=reduced, peaks=peaks, window_s=window_s)
        for m in cell["per_layer"]:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = dict(value=value,
                                                    unit=m["unit"])
    result["limits"] = {k: dict(value=verdict[k], limit=LIMITS[k])
                        for k in LIMITS}
    return result
