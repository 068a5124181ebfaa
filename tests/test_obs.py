"""Observability subsystem (DESIGN.md §16): metrics registry semantics,
span-tracer ring buffer + Chrome trace export, no-op identities, engine
instrumentation parity (observe on == observe off, byte-for-byte),
service-layer metrics with the pure-observer cache-key discipline, spans
in a profiler trace, JAX compile counters, and the device programs'
names."""
import dataclasses
import gc
import glob
import json
import os
import re
import subprocess
import sys
import textwrap
import time
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.clique import make_clique_computation
from repro.core.engine import Engine, EngineConfig
from repro.data.synthetic_graphs import densifying_graph
from repro.obs import (NOOP, NULL_METRIC, NULL_REGISTRY, NULL_SPAN,
                       NULL_TRACER, MetricsRegistry, Observability,
                       SpanTracer, log_buckets)
from repro.obs.metrics import DEFAULT_TIME_BUCKETS
from repro.service import DiscoveryRequest, DiscoveryService


# -------------------------------------------------------------- log_buckets
def test_log_buckets_exact_decades():
    assert log_buckets(1e-3, 1.0, per_decade=1) == \
        pytest.approx((1e-3, 1e-2, 1e-1, 1.0))


def test_log_buckets_per_decade_and_validation():
    b = log_buckets(1e-2, 1.0, per_decade=2)
    assert len(b) == 5 and b[0] == pytest.approx(1e-2) \
        and b[-1] == pytest.approx(1.0)
    with pytest.raises(ValueError):
        log_buckets(0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(1.0, 0.5)
    with pytest.raises(ValueError):
        log_buckets(1e-3, 1.0, per_decade=0)


def test_default_time_buckets_span_and_monotone():
    b = DEFAULT_TIME_BUCKETS
    assert b[0] == pytest.approx(1e-6) and b[-1] == pytest.approx(100.0)
    assert all(nxt > cur for cur, nxt in zip(b, b[1:]))


# ---------------------------------------------------------- metric semantics
def test_counter_monotone():
    r = MetricsRegistry()
    c = r.counter("c_total", "help text")
    c.inc()
    c.inc(4)
    c.inc(0.5)
    assert c.value == pytest.approx(5.5)
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_last_write_wins():
    g = MetricsRegistry().gauge("g")
    g.set(7)
    g.inc(3)
    g.set(2)
    assert g.value == 2


def test_histogram_le_semantics():
    # `le` is an *inclusive* upper edge: a value exactly on a bound lands
    # in that bound's bucket, one ulp above lands in the next
    h = MetricsRegistry().histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0):        # both <= 1.0
        h.observe(v)
    h.observe(1.0000001)        # (1, 10]
    h.observe(100.0)            # (10, 100]
    h.observe(1e9)              # +Inf overflow bucket
    snap = h.snapshot()
    assert snap["counts"] == [2, 1, 1, 1]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(0.5 + 1.0 + 1.0000001 + 100.0 + 1e9)


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError):
        MetricsRegistry().histogram("h", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        MetricsRegistry().histogram("h2", buckets=(2.0, 1.0))


def test_registry_get_or_create_and_kind_clash():
    r = MetricsRegistry()
    assert r.counter("x") is r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")
    assert r.get("x").kind == "counter"
    assert r.get("missing") is None
    r.gauge("a_gauge")
    assert r.names() == ["a_gauge", "x"]


def test_prometheus_exposition_round_trips():
    r = MetricsRegistry()
    r.counter("steps_total", "total steps").inc(42)
    r.gauge("occupancy").set(17)
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = r.to_prometheus()
    lines = text.strip().splitlines()
    assert "# HELP steps_total total steps" in lines
    assert "# TYPE steps_total counter" in lines
    assert "steps_total 42" in lines
    assert "occupancy 17" in lines
    # histogram buckets are cumulative and end with +Inf == count
    assert 'lat_seconds_bucket{le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{le="1"} 2' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 3' in lines
    assert "lat_seconds_count 3" in lines
    # sample values round-trip through float()
    for line in lines:
        if not line.startswith("#"):
            float(line.rsplit(" ", 1)[1])


# ------------------------------------------------------------------- tracer
def test_tracer_records_spans_with_duration():
    t = SpanTracer(capacity=16)
    with t.span("phase.a"):
        pass
    with t.span("phase.b"):
        with t.span("phase.a"):
            pass
    spans = t.spans()
    assert [s[0] for s in spans] == ["phase.a", "phase.a", "phase.b"]
    assert all(s[2] >= 0 for s in spans)
    # nested span closed first, so it precedes its parent in the buffer
    assert t.total_recorded == 3 and t.dropped == 0


def test_tracer_records_span_when_body_raises():
    t = SpanTracer(capacity=4)
    with pytest.raises(RuntimeError):
        with t.span("doomed"):
            raise RuntimeError("boom")
    assert [s[0] for s in t.spans()] == ["doomed"]


def test_tracer_ring_wraparound():
    t = SpanTracer(capacity=4)
    for i in range(10):
        t._record(f"s{i}", float(i), 0.001)
    assert t.total_recorded == 10
    assert t.dropped == 6
    # retained window is the newest 4, oldest first
    assert [s[0] for s in t.spans()] == ["s6", "s7", "s8", "s9"]
    t.clear()
    assert t.spans() == [] and t.total_recorded == 0


def test_chrome_trace_export(tmp_path):
    t = SpanTracer(capacity=8)
    with t.span("engine.step"):
        pass
    path = t.export_chrome_trace(str(tmp_path / "sub" / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == 1
    ev = doc["traceEvents"][0]
    assert ev["name"] == "engine.step" and ev["ph"] == "X"
    for key in ("ts", "dur", "pid", "tid"):
        assert isinstance(ev[key], (int, float))
    assert ev["dur"] >= 0


# ---------------------------------------------------------------- no-op path
def test_noop_identities():
    assert NOOP.enabled is False
    assert NOOP.metrics is NULL_REGISTRY
    assert NOOP.tracer is NULL_TRACER
    # every metric resolves to the one shared null object
    assert NOOP.counter("anything") is NULL_METRIC
    assert NOOP.gauge("g") is NULL_METRIC
    assert NOOP.histogram("h") is NULL_METRIC
    # and the one shared null span
    assert NOOP.tracer.span("s") is NULL_SPAN
    with NOOP.span("s"):
        pass
    NULL_METRIC.inc()
    NULL_METRIC.set(3)
    NULL_METRIC.observe(0.5)
    assert NULL_METRIC.value == 0 and NULL_METRIC.count == 0
    assert NOOP.tracer.spans() == [] and NOOP.tracer.total_recorded == 0
    assert NULL_REGISTRY.to_prometheus() == ""


def test_noop_export_writes_empty_trace(tmp_path):
    path = NOOP.tracer.export_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        assert json.load(f)["traceEvents"] == []


def test_snapshot_shapes():
    obs = Observability(max_spans=8)
    obs.counter("c").inc(2)
    with obs.span("s"):
        pass
    snap = obs.snapshot()
    assert snap["enabled"] is True
    assert snap["metrics"]["c"]["value"] == 2
    assert snap["spans"] == {"recorded": 1, "dropped": 0, "capacity": 8}
    json.dumps(snap)   # JSON-serializable end to end
    noop_snap = NOOP.snapshot()
    assert noop_snap["enabled"] is False and noop_snap["metrics"] == {}


# ----------------------------------------------- engine instrumentation
@pytest.fixture(scope="module")
def clique_setup():
    """Spill + refill + late pruning all active (the instrumented paths)."""
    g = densifying_graph(96, 900, seed=0)
    comp = make_clique_computation(g)
    cfg = EngineConfig(k=3, batch=8, pool_capacity=128, max_steps=100_000)
    ref = Engine(comp, cfg).run()
    assert ref.spilled > 0 and ref.refilled > 0
    return comp, cfg, ref


def _require_devices(n: int) -> None:
    if len(jax.devices()) < n:
        pytest.skip(f"needs >= {n} devices (force host devices with "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count={n})")


def _assert_parity(ref, res):
    assert np.array_equal(ref.result_keys, res.result_keys)
    assert np.array_equal(ref.result_states, res.result_states)


@pytest.mark.parametrize("shards", [1, 2, 8])
@pytest.mark.parametrize("T", [1, 16])
def test_observe_parity(clique_setup, shards, T):
    """observe=True is a pure observer: results are byte-identical to the
    unobserved run at every shard count and fusion factor."""
    _require_devices(shards)
    comp, cfg, ref = clique_setup
    obs_cfg = dataclasses.replace(cfg, steps_per_sync=T, observe=True)
    if shards == 1:
        eng = Engine(comp, obs_cfg)
    else:
        from repro.distributed import ShardedEngine
        eng = ShardedEngine(comp, dataclasses.replace(
            obs_cfg, shards=shards))
    res = eng.run()
    _assert_parity(ref, res)
    # the observer actually observed
    m = eng.obs.metrics
    assert m.get("engine_steps_total").value == res.steps
    assert m.get("engine_candidates_total").value > 0
    assert m.get("vpq_spilled_entries_total").value == res.spilled
    assert eng.obs.tracer.total_recorded > 0
    names = {s[0] for s in eng.obs.tracer.spans()}
    assert {"engine.start", "engine.step", "engine.dispatch",
            "engine.wait", "engine.finalize"} <= names


def test_observe_off_records_nothing(clique_setup):
    comp, cfg, ref = clique_setup
    eng = Engine(comp, cfg)    # observe defaults off
    res = eng.run()
    _assert_parity(ref, res)
    assert eng.obs is NOOP
    assert eng.obs.tracer.total_recorded == 0


def test_shared_observability_across_engines(clique_setup):
    """EngineConfig.observability injects a shared registry — two engines
    accumulate into the same counters (the service-process pattern)."""
    comp, cfg, _ref = clique_setup
    shared = Observability()
    for _ in range(2):
        Engine(comp, dataclasses.replace(
            cfg, observe=True, observability=shared)).run()
    steps = shared.metrics.get("engine_steps_total").value
    single = Engine(comp, dataclasses.replace(cfg, observe=True))
    single.run()
    assert steps == 2 * single.obs.metrics.get("engine_steps_total").value


def test_checkpoint_spans_and_metrics(clique_setup, tmp_path):
    comp, cfg, ref = clique_setup
    eng = Engine(comp, dataclasses.replace(
        cfg, observe=True, checkpoint_every=20,
        checkpoint_dir=str(tmp_path)))
    res = eng.run()
    _assert_parity(ref, res)
    m = eng.obs.metrics
    assert m.get("checkpoint_saves_total").value > 0
    assert m.get("checkpoint_bytes_written_total").value > 0
    assert m.get("checkpoint_commit_seconds").count > 0
    names = {s[0] for s in eng.obs.tracer.spans()}
    assert {"checkpoint.save", "checkpoint.capture",
            "checkpoint.commit"} <= names


# ------------------------------------------------------------ service layer
@pytest.fixture(scope="module")
def social():
    return densifying_graph(80, 400, seed=3)


def _service(social, **kw):
    svc = DiscoveryService(**kw)
    svc.register_graph("social", social)
    return svc


def test_observe_excluded_from_cache_key(social):
    """observe is a pure observer (same discipline as checkpointing): two
    requests differing only in observe share one cache entry."""
    base = dict(graph="social", workload="clique", k=3, step_budget=50)
    req_off = DiscoveryRequest(**base)
    req_on = DiscoveryRequest(**base, observe=True)
    assert req_off.canonical_spec() == req_on.canonical_spec()
    assert "observe" not in req_on.canonical_spec()

    svc = _service(social, observability=Observability())
    r1 = svc.query(req_on)
    r2 = svc.query(req_off)
    assert r1.status == r2.status == "ok"
    assert not r1.cached and r2.cached
    assert r1.results == r2.results
    assert svc.obs.metrics.get("service_cache_hits_total").value == 1
    assert svc.obs.metrics.get("service_cache_misses_total").value == 1


def test_service_metrics_accumulate(social):
    svc = _service(social, observability=Observability())
    ok = svc.query(DiscoveryRequest(graph="social", workload="clique",
                                    k=3, step_budget=40, observe=True))
    assert ok.status == "ok"
    bad = svc.query(DiscoveryRequest(graph="nope", workload="clique", k=3))
    assert bad.status == "error"
    m = svc.obs.metrics
    assert m.get("service_requests_total").value == 2
    assert m.get("service_validation_errors_total").value == 1
    assert m.get("service_request_seconds").count >= 1
    assert m.get("service_queue_wait_seconds").count >= 1
    # engine steps flowed into the shared registry via the observe knob
    assert m.get("service_engine_steps_total").value == \
        m.get("engine_steps_total").value > 0
    assert ok.stats["straggler_steps"] == 0


def test_service_default_is_noop(social):
    svc = _service(social)
    assert svc.obs is NOOP
    resp = svc.query(DiscoveryRequest(graph="social", workload="clique",
                                      k=3, step_budget=40))
    assert resp.status == "ok"
    assert NOOP.tracer.total_recorded == 0


# ------------------------------------------------- spans in a profiler trace
def _host_events(trace_dir):
    """Host events of the profiler trace under ``trace_dir``:
    ``(line, name, start_ns, end_ns, stats)`` rows."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = {}
                if e.name == "service.task_step":
                    with warnings.catch_warnings():   # event_stats' type
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(e.stats)
                out.append((line.name, e.name, e.start_ns,
                            e.start_ns + e.duration_ns, stats))
    return out


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory):
    """A profiler trace of one observed service batch of two clique
    queries whose pools spill and refill, and the batch's answers."""
    g = densifying_graph(96, 900, seed=0)
    svc = DiscoveryService(observability=Observability())
    svc.register_graph("g", g)
    reqs = [DiscoveryRequest(graph="g", workload="clique", k=k, batch=8,
                             pool_capacity=128, observe=True,
                             request_id=f"q{k}") for k in (3, 4)]
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    resps = svc.serve(reqs)
    jax.profiler.stop_trace()
    assert all(r.status == "ok" for r in resps)
    assert all(r.stats["spilled"] > 0 and r.stats["refilled"] > 0
               for r in resps)
    return _host_events(trace_dir), resps, reqs


def _inside(child, parent):
    return (child[0] == parent[0] and parent[2] <= child[2]
            and child[3] <= parent[3])


@pytest.mark.parametrize("child,parent", [
    ("service.task_step", "service.drive"),
    ("engine.step", "service.task_step"),
    ("engine.dispatch", "engine.step"),
    ("engine.wait", "engine.step"),
    ("engine.fetch_overflow", "engine.step"),
    ("engine.refill", "engine.step"),
    ("vpq.pop", "engine.refill"),
    ("engine.finalize", "service.finalize"),
])
def test_spans_nest_in_the_profiler_trace(served_trace, child, parent):
    events, _resps, _reqs = served_trace
    children = [e for e in events if e[1] == child]
    parents = [e for e in events if e[1] == parent]
    assert children and parents
    assert all(any(_inside(c, p) for p in parents) for c in children)


def test_vpq_push_times_the_push_inside_the_fetch(served_trace):
    events, _resps, _reqs = served_trace
    fetches = [e for e in events if e[1] == "engine.fetch_overflow"]
    pushes = [e for e in events if e[1] == "vpq.push"]
    assert any(_inside(p, f) for p in pushes for f in fetches)


def test_engine_built_and_started_outside_the_drive(served_trace):
    events, _resps, _reqs = served_trace
    drives = [e for e in events if e[1] == "service.drive"]
    for name in ("service.build_engine", "engine.start"):
        spans = [e for e in events if e[1] == name]
        assert len(spans) == 2                     # one per query
        assert not any(_inside(s, d) for s in spans for d in drives)


def test_task_step_carries_the_request_id(served_trace):
    events, resps, reqs = served_trace
    steps = [e for e in events if e[1] == "service.task_step"]
    ids = {e[4].get("request_id") for e in steps}
    assert ids == {r.request_id for r in reqs}
    # one task number per query, shared by all of its steps
    by_id = {}
    for e in steps:
        by_id.setdefault(e[4]["request_id"], set()).add(e[4]["task"])
    assert all(len(t) == 1 for t in by_id.values())
    assert sum(r.stats["host_syncs"] for r in resps) == len(steps)


def test_observed_service_answers_as_unobserved(served_trace):
    g = densifying_graph(96, 900, seed=0)
    _events, resps, reqs = served_trace
    svc = DiscoveryService()
    svc.register_graph("g", g)
    plain = svc.serve([dataclasses.replace(r, observe=False) for r in reqs])
    for a, b in zip(resps, plain):
        assert json.dumps(a.results) == json.dumps(b.results)
        assert a.result_keys == b.result_keys
        # every count but the wall-clock straggler flag
        a.stats.pop("straggler_steps")
        b.stats.pop("straggler_steps")
        assert a.stats == b.stats


def test_span_without_profiler_records_ring_only():
    t = SpanTracer(capacity=4)
    with t.span("service.task_step", request_id="r1", task=7):
        pass
    assert [s[0] for s in t.spans()] == ["service.task_step"]
    assert NOOP.span("x", request_id="r") is NULL_SPAN


# ------------------------------------------------------ JAX compile counters
JAX_SECONDS = ("jax_trace_seconds_total", "jax_lower_seconds_total",
               "jax_backend_compile_seconds_total")


def _jax_seconds(obs):
    return {n: obs.metrics.get(n).value for n in JAX_SECONDS}


def _fresh_jit():
    """Compile and run a program no other test has: a new lambda is a new
    cache entry for ``jax.jit``."""
    jax.jit(lambda x: x * 3 + 1)(np.arange(7, dtype=np.int32)
                                 ).block_until_ready()


def test_jax_counters_count_a_fresh_jit_only():
    svc = DiscoveryService(observability=Observability())
    try:
        assert set(_jax_seconds(svc.obs).values()) == {0}
        f = jax.jit(lambda x: x * 3 + 1)
        x = np.arange(7, dtype=np.int32)
        f(x).block_until_ready()
        first = _jax_seconds(svc.obs)
        assert all(v > 0 for v in first.values())
        f(x).block_until_ready()                  # cached: no new work
        assert _jax_seconds(svc.obs) == first
    finally:
        svc.close()


def test_nested_traces_count_their_seconds_once():
    """A jit traced inside its caller's trace reports a trace of its own,
    but its seconds lie inside the caller's and are not added again."""
    def nest(depth):
        if depth == 0:
            return lambda x: jnp.sin(x) * 2
        inner = jax.jit(nest(depth - 1))
        return lambda x: inner(x) + inner(x + 1)

    svc = DiscoveryService(observability=Observability())
    try:
        f = jax.jit(nest(8))
        t0 = time.perf_counter()
        f.trace(np.ones(4, np.float32))
        wall = time.perf_counter() - t0
        assert 0 < _jax_seconds(svc.obs)["jax_trace_seconds_total"] <= wall
    finally:
        svc.close()


def test_close_stops_counting():
    svc = DiscoveryService(observability=Observability())
    svc.close()
    svc.close()                                   # idempotent
    _fresh_jit()
    assert set(_jax_seconds(svc.obs).values()) == {0}


def test_dropped_service_stops_counting():
    obs = Observability()
    svc = DiscoveryService(observability=obs)
    del svc
    gc.collect()
    _fresh_jit()
    assert set(_jax_seconds(obs).values()) == {0}


@pytest.mark.parametrize("owner", ["noop_service", "observed_engine"])
def test_only_an_observed_service_counts_compiles(clique_setup, owner):
    """An unobserved service counts nothing, and an observed engine's own
    registry holds spans and metrics only: the listeners are the
    process-owning service's."""
    if owner == "noop_service":
        obs = DiscoveryService().obs
        assert obs is NOOP
    else:
        comp, cfg, _ref = clique_setup
        obs = Engine(comp, dataclasses.replace(cfg, observe=True)).obs
        assert obs.enabled
    _fresh_jit()
    assert all(obs.metrics.get(n) is None for n in JAX_SECONDS)


# ------------------------------------------------------------- engine builds
def test_engine_builds_count_cache_misses(social):
    svc = _service(social, observability=Observability())
    builds = svc.obs.metrics.get("service_engine_builds_total")
    rng = np.random.default_rng(0)

    def req(weights, k=3, **kw):
        return DiscoveryRequest(graph="social", workload="weighted-clique",
                                k=k, weights=weights, observe=True, **kw)

    w1, w2 = (tuple(int(x) for x in rng.integers(1, 100, social.n))
              for _ in range(2))
    svc.serve([req(w1), req(w2)])
    assert builds.value == 1            # weights are per-query tables
    svc.serve([req(w1, use_cache=False)])         # engine reused
    assert builds.value == 1
    svc.serve([req(w1)])                          # result-cache hit
    assert builds.value == 1
    svc.serve([req(w2, k=4)])           # k shapes the program: a new engine
    assert builds.value == 2


# ----------------------------------------------------- device program names
def _normalized(hlo_text):
    """Lowered text without module name, op-name metadata and locations."""
    text = re.sub(r"module @\S+", "module", hlo_text)
    text = re.sub(r"jit\([\w.]+\)", "jit()", text)
    return re.sub(r"\s*loc\(.*", "", text)


def _module_name(lowered):
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def test_engine_programs_are_named(clique_setup):
    comp, cfg, _ref = clique_setup
    eng1 = Engine(comp, cfg)
    eng4 = Engine(comp, dataclasses.replace(cfg, steps_per_sync=4))
    st = eng1.start()
    pool = (st.pool_states, st.pool_prio, st.pool_ub)
    args = pool + (st.result_states, st.result_keys, comp.tables)
    new = tuple(a[:4] for a in pool)
    lowered = {
        "jit_discovery_init": (eng1._init.lower(comp.tables),
                               jax.jit(comp.init_frontier).lower(
                                   comp.tables)),
        "jit_discovery_step": (eng1._step.lower(*args),
                               jax.jit(eng1._step_impl).lower(*args)),
        "jit_discovery_insert": (eng1._insert.lower(*pool, *new),
                                 jax.jit(eng1._insert_impl).lower(*pool,
                                                                  *new)),
        "jit_discovery_macro": (
            eng4._macro.lower(*args, np.int32(4), False, np.int32(3)),
            jax.jit(eng4._macro_impl).lower(*args, np.int32(4), False,
                                            np.int32(3))),
    }
    for name, (named, plain) in lowered.items():
        assert _module_name(named) == name
        # the same computation as the unnamed jit of the same function
        assert _normalized(named.as_text()) == _normalized(plain.as_text())


def test_sharded_programs_are_named_on_four_devices():
    prog = """
        import dataclasses, re
        import numpy as np
        import jax
        from repro.core.clique import make_clique_computation
        from repro.core.engine import EngineConfig
        from repro.data.synthetic_graphs import densifying_graph
        from repro.distributed import ShardedEngine
        assert len(jax.devices()) == 4
        comp = make_clique_computation(densifying_graph(64, 400, seed=0))
        cfg = EngineConfig(k=3, batch=8, pool_capacity=64, shards=4)
        names = []
        for kw in (dict(), dict(steps_per_sync=4)):
            eng = ShardedEngine(comp, dataclasses.replace(cfg, **kw))
            st = eng.start()
            args = (st.pool_states, st.pool_prio, st.pool_ub,
                    st.result_states, st.result_keys, eng.tables)
            if eng.T == 1:
                lowered = [eng._step_sharded.lower(*args),
                           eng._insert_sharded.lower(*args[:3], *args[:3])]
            else:
                lowered = [eng._macro_sharded.lower(
                    *args, np.int32(4), np.zeros(4, bool),
                    st.pool_occupancy.astype(np.int32))]
            names += [re.search(r"module @(\\S+)", l.as_text()).group(1)
                      for l in lowered]
        print(" ".join(names))
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), "src"),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(prog)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == ["jit_discovery_step_sharded",
                                  "jit_discovery_insert_sharded",
                                  "jit_discovery_macro_sharded"]


def test_masked_intersect_kernel_is_named():
    from repro.kernels.masked_intersect import masked_intersect
    a = np.ones((8, 4), np.uint32)
    text = jax.jit(lambda a, b: masked_intersect(a, b, interpret=True)
                   ).lower(a, a).as_text()
    assert "masked_intersect" in text
