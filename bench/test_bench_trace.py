"""The trace reduction and the roofline's work counts, against hand
counts and against a trace recorded on a TPU."""
import gzip
import json
import os

import pytest

from bench import devtrace, roofline
from bench.devtrace import Event

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "testdata", "small.xplane.pb")
SMALL_JSON = os.path.join(HERE, "testdata", "small.trace.json.gz")


def op(dev, name, s, e):
    return Event(f"/device:TPU:{dev}", "XLA Ops", name, s, e)


def host(name, s, e, line="python"):
    return Event("/host:CPU", line, name, s, e)


def test_union_and_gaps():
    merged = devtrace.union([(5, 15), (0, 10), (20, 30), (30, 31), (40, 40)])
    assert merged == [(0, 15), (20, 31)]
    assert devtrace.gaps(merged, -5, 50) == [(-5, 0), (15, 20), (31, 50)]


def test_reduce_hand_trace():
    # device 0 busy [0,15] and [20,30]; device 1 busy [10,20]
    events = [op(0, "%fusion.1 = s32[64,4096]{1,0} fusion(...)", 0, 10),
              op(0, "%sort.2 = (s32[8]{0})", 5, 15),
              op(0, "%fusion.1 = s32[64,4096]{1,0} fusion(...)", 20, 30),
              op(1, "%fusion.1 = s32[64,4096]{1,0} fusion(...)", 10, 20),
              Event("/device:TPU:0", "XLA Modules", "jit_step", 0, 30),
              host("bench.window", -10, 50),
              host("bench.serve", 0, 40),
              host("np.asarray", 14, 18, line="pjrt"),
              host("outside", 60, 70)]
    r = devtrace.reduce_events(events, 0.0, 40.0,
                               exclude_host=("bench.window",))
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["busy_by_device"] == {"/device:TPU:0": pytest.approx(25e-9),
                                   "/device:TPU:1": pytest.approx(10e-9)}
    assert r["busy_s"] == pytest.approx(17.5e-9)
    # op seconds summed over devices, over the 2 devices
    assert r["device_ops"] == [["%fusion.1 = s32[64,4096]",
                                pytest.approx(15e-9)],
                               ["%sort.2 = (s32[8]", pytest.approx(5e-9)]]
    # device 0 idle [15,20] (np.asarray to 18, then bench.serve) and
    # [30,40] (bench.serve); device 1 idle [0,10] (bench.serve, with
    # nothing inner) and [20,40] (bench.serve): halved over 2 devices
    idle = dict((k, v) for k, v in r["idle_gaps"])
    assert idle["np.asarray"] == pytest.approx(3e-9 / 2)
    assert idle["bench.serve"] == pytest.approx((2 + 10 + 10 + 20) * 1e-9 / 2)
    assert sum(idle.values()) == pytest.approx(
        2 * 40e-9 / 2 - r["busy_s"])


def test_idle_without_host_event_is_host_idle():
    events = [op(0, "a", 0, 10), host("bench.serve", 0, 5)]
    r = devtrace.reduce_events(events, 0.0, 20.0)
    assert r["idle_gaps"] == [["host idle", pytest.approx(10e-9)]]


def test_no_device_op_reads_nothing():
    assert devtrace.reduce_events([host("bench.serve", 0, 5)], 0, 5) is None


def _chrome_busy(path):
    """Busy seconds and op seconds of /device:TPU:0 inside the window,
    from the Chrome-format copy of the trace (an independent reading)."""
    with gzip.open(path) as f:
        data = json.load(f)
    ev = data["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in ev
             if e.get("ph") == "M" and e["name"] == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in ev
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    win = [e for e in ev if e.get("ph") == "X" and e["name"] == "bench.window"]
    assert len(win) == 1
    t0, t1 = win[0]["ts"], win[0]["ts"] + win[0]["dur"]
    iv, names = [], {}
    for e in ev:
        if (e.get("ph") == "X" and procs.get(e["pid"]) == "/device:TPU:0"
                and threads.get((e["pid"], e["tid"])) == "XLA Ops"):
            s, t = max(e["ts"], t0), min(e["ts"] + e["dur"], t1)
            if t > s:
                iv.append((s, t))
                long_name = e.get("args", {}).get("long_name", e["name"])
                key = long_name.split("{", 1)[0].strip()
                names[key] = names.get(key, 0) + (t - s) / 1e6
    iv.sort()
    busy, end = 0.0, None
    for s, t in iv:
        if end is None or s > end:
            busy += t - s
            end = t
        elif t > end:
            busy += t - end
            end = t
    return busy / 1e6, (t1 - t0) / 1e6, names


def test_recorded_tpu_trace():
    events = devtrace.load_events(SMALL)
    t0, t1 = devtrace.window_of(events, "bench.window")
    r = devtrace.reduce_events(events, t0, t1, exclude_host=("bench.window",))
    busy, window, names = _chrome_busy(SMALL_JSON)
    assert r["window_s"] == pytest.approx(window, abs=1e-5)
    assert r["busy_s"] == pytest.approx(busy, rel=1e-3, abs=1e-5)
    assert 0 < r["busy_s"] < r["window_s"]
    top = sorted(names.items(), key=lambda kv: -kv[1])
    assert [k for k, _ in r["device_ops"][:3]] == [k for k, _ in top[:3]]
    assert [v for _, v in r["device_ops"][:3]] == pytest.approx(
        [v for _, v in top[:3]], rel=1e-3, abs=1e-6)
    # the host slept between the products: charged to its annotation
    idle = dict((k, v) for k, v in r["idle_gaps"])
    assert idle.get("bench.wait", 0) >= 3 * 0.004


def test_expand_step_bytes_hand_count():
    # n=64 vertices, w=2 words: extension table 128 words, weights 64
    # words, 4 dequeued states of 6 words 24 words: 216 words of 4 bytes
    assert roofline.expand_step_bytes(64, 2, 4, 6) == 864
    assert roofline.expand_step_bytes(4096, 128, 64, 258) == \
        4 * (4096 * 128 + 4096 + 64 * 258)


def test_peaks_table():
    p = roofline.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    assert roofline.least_seconds(819e9, p) == 1.0
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9 imaginary")
