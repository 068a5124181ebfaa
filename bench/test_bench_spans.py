"""The reductions by program name (``spans.py``) and the per-layer
readers that use them, on hand-made events, on a synthetic ``ctx`` and
on the trace recorded on a TPU."""
import gzip
import json
import os
import shutil
import statistics

import pytest

from bench import devtrace, harness, spans
from bench.devtrace import Event

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "testdata", "small.xplane.pb")
SMALL_JSON = os.path.join(HERE, "testdata", "small.trace.json.gz")


def module(dev, name, s, e):
    return Event(f"/device:TPU:{dev}", "XLA Modules", name, s, e)


def host(name, s, e, line="bench-window"):
    return Event("/host:CPU", line, name, s, e)


def test_program_name_strips_the_hash():
    assert spans.program_name("jit_sort(13817360775552291634)") == "jit_sort"
    assert spans.program_name("jit_discovery_step") == "jit_discovery_step"
    assert spans.program_name("jit_f(x)") == "jit_f(x)"


def test_program_seconds_hand_trace():
    events = [module(0, "jit_discovery_step(1)", 0, 10),
              module(0, "jit_discovery_step(1)", 20, 30),
              module(1, "jit_discovery_step(1)", 0, 10),
              module(1, "jit_discovery_insert(2)", 35, 50),   # clipped
              module(0, "jit_discovery_step(1)", 60, 70),     # outside
              Event("/device:TPU:0", "XLA Ops", "%fusion.1", 0, 10),
              host("engine.step", 0, 40)]
    secs = spans.program_seconds(events, 0.0, 40.0)
    # over the two devices that ran a program in the window
    assert secs == {"jit_discovery_step": pytest.approx(30e-9 / 2),
                    "jit_discovery_insert": pytest.approx(5e-9 / 2)}
    assert spans.program_seconds(events, 100.0, 200.0) == {}


def test_span_intervals_hand_trace():
    events = [host("engine.step", 0, 10), host("engine.wait", 2, 5),
              host("engine.wait", 6, 8),
              host("engine.step", 12, 20), host("engine.wait", 13, 19),
              host("engine.wait", 19, 25),        # crosses the step's end
              host("engine.step", 30, 40, line="other"),
              host("engine.wait", 31, 32, line="other"),
              host("engine.wait", 33, 34),        # no step on this thread
              host("engine.step", -10, -1),       # starts before the window
              Event("/device:TPU:0", "XLA Ops", "engine.step", 0, 10)]
    got = spans.span_intervals(events, 0.0, 50.0, "engine.step",
                               child="engine.wait")
    assert got == [((0, 10), [(2, 5), (6, 8)]), ((12, 20), [(13, 19)]),
                   ((30, 40), [(31, 32)])]
    assert spans.span_intervals(events, 0.0, 50.0, "engine.step") == [
        ((0, 10), []), ((12, 20), []), ((30, 40), [])]
    assert spans.span_intervals(events, 0.0, 50.0, "missing") == []


def _chrome_modules(path):
    """Seconds per program on /device:TPU:0's XLA Modules line inside the
    window, from the Chrome-format copy of the trace."""
    with gzip.open(path) as f:
        ev = json.load(f)["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in ev
             if e.get("ph") == "M" and e["name"] == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in ev
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    win, = [e for e in ev if e.get("ph") == "X" and
            e["name"] == "bench.window"]
    t0, t1 = win["ts"], win["ts"] + win["dur"]
    out = {}
    for e in ev:
        if (e.get("ph") == "X" and procs.get(e["pid"]) == "/device:TPU:0"
                and threads.get((e["pid"], e["tid"])) == "XLA Modules"):
            s, t = max(e["ts"], t0), min(e["ts"] + e["dur"], t1)
            if t > s:
                name = e["name"].split("(")[0]
                out[name] = out.get(name, 0) + (t - s) / 1e6
    return out


def test_program_seconds_recorded_tpu_trace():
    events = devtrace.load_events(SMALL)
    t0, t1 = devtrace.window_of(events, "bench.window")
    secs = spans.program_seconds(events, t0, t1)
    assert set(secs) == {"jit__lambda", "jit_sort"}
    assert secs == pytest.approx(_chrome_modules(SMALL_JSON), rel=1e-3,
                                 abs=1e-6)
    # three sorts of about 1.27 ms each, two of the three products inside
    assert secs["jit_sort"] == pytest.approx(3 * 1.266e-3, rel=0.01)
    assert secs["jit__lambda"] == pytest.approx(2 * 0.114e-3, rel=0.01)
    # the harness's annotation is a span like any other
    waits = spans.span_intervals(events, t0, t1, "bench.wait")
    assert len(waits) == 3 and all(c == [] for _, c in waits)


def test_window_loads_the_cells_trace_once(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "SCRATCH", str(tmp_path))
    ctx = dict(cell=dict(name="cell.x"), trace=dict(busy_s=1.0))
    assert spans.window(ctx) is None             # no trace written
    d = tmp_path / "trace" / "cell.x" / "plugins"
    d.mkdir(parents=True)
    shutil.copy(SMALL, d / "host.xplane.pb")
    events, t0, t1 = spans.window(ctx)
    assert (t0, t1) == devtrace.window_of(events, harness.WINDOW_SPAN)
    assert spans.window(ctx)[0] is events        # loaded once
    assert spans.window(dict(ctx, trace=None)) is None
    assert spans.window(dict(events=[], t0=1, t1=2)) == ([], 1, 2)


def records(n_ran=4, n_cached=1):
    return ([dict(status="ok", cached=False, stats={})] * n_ran +
            [dict(status="ok", cached=True, stats={})] * n_cached +
            [dict(status="error", cached=False, stats={})])


def step_events():
    """Three engine steps of 10, 12 and 30 ns holding waits of 4, 2+2
    and 5 ns: host parts 6, 8 and 25 ns; two programs on each of two
    devices."""
    return [host("engine.step", 0, 10), host("engine.wait", 3, 7),
            host("engine.step", 20, 32), host("engine.wait", 21, 23),
            host("engine.wait", 24, 26),
            host("engine.step", 40, 70), host("engine.wait", 50, 55),
            module(0, "jit_discovery_step(9)", 1, 7),
            module(1, "jit_discovery_step(9)", 1, 6),
            module(0, "jit_discovery_macro_sharded(3)", 21, 26),
            module(1, "jit_discovery_macro_sharded(3)", 21, 26),
            module(0, "jit_discovery_insert(4)", 50, 90)]


@pytest.mark.parametrize("name,value", [
    ("engine_builds_per_query", 6 / 4),
    ("jit_s_per_query", (1.5 + 0.5 + 2.0) / 4),
    ("step_host_ms", 8e-6),
    ("step_device_ms", 1e3 * ((6 + 5 + 5 + 5) * 1e-9 / 2) / 3),
])
def test_span_readers_on_a_synthetic_ctx(name, value):
    ctx = dict(cell=dict(name="c"), records=records(), trace=None,
               counters={"service_engine_builds_total": 6,
                         "jax_trace_seconds_total": 1.5,
                         "jax_lower_seconds_total": 0.5,
                         "jax_backend_compile_seconds_total": 2.0,
                         "engine_steps_total": 3},
               events=step_events(), t0=0.0, t1=80.0)
    assert harness.metric_reader(name)(ctx) == pytest.approx(value)


def test_step_host_ms_is_the_median_of_host_parts():
    ctx = dict(events=step_events(), t0=0.0, t1=80.0)
    assert harness.metric_reader("step_host_ms")(ctx) == pytest.approx(
        statistics.median([6, 8, 25]) / 1e6)


@pytest.mark.parametrize("name", ["step_host_ms", "step_device_ms"])
def test_span_readers_read_nothing_in_an_unnamed_trace(name):
    """The recorded trace has neither the program's spans nor its named
    programs, as a trace of a program without them would not."""
    events = devtrace.load_events(SMALL)
    t0, t1 = devtrace.window_of(events, "bench.window")
    ctx = dict(cell=dict(name="c"), records=records(), trace=dict(),
               counters={"engine_steps_total": 10}, events=events, t0=t0,
               t1=t1)
    assert harness.metric_reader(name)(ctx) is None


@pytest.mark.parametrize("name", ["engine_builds_per_query",
                                  "jit_s_per_query"])
def test_counter_readers_read_nothing_without_counters(name):
    ctx = dict(records=records(), counters={"engine_steps_total": 10})
    assert harness.metric_reader(name)(ctx) is None
    ctx = dict(records=records(0, 2), counters={
        "service_engine_builds_total": 1, "jax_trace_seconds_total": 1,
        "jax_lower_seconds_total": 1,
        "jax_backend_compile_seconds_total": 1})
    assert harness.metric_reader(name)(ctx) is None   # nothing executed
