"""A whole run of a cell on the CPU at a small graph size, and the
per-layer readers on a run's records."""
import time

import pytest

from bench import harness


def small_cell(name="mico-wclique.c4", n=256):
    bench = harness.load_benchmark()
    cell = harness.resolve(bench, name)
    cell["config"] = dict(cell["config"], n=n, m=round(10.8 * n))
    return cell


def test_run_is_correct_and_reports_its_metrics():
    lines = []
    res = harness.run_cell(small_cell(), 2 ** 31 + 11, 0.5, False,
                           time.perf_counter(), require_tpu=False,
                           emit=lines.append)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 4            # one batch of the 4 clients
    assert set(res["metrics"]) == {m["name"] for m in
                                   small_cell()["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "limits"
    assert res["limits"] == {k: dict(value=0, limit=0)
                             for k in harness.LIMITS}
    assert {k for line in lines for k in line} == {"setup", "window",
                                                  "check"}
    engine = next(line["window"]["engine"] for line in lines
                  if "window" in line)
    assert engine["executed"] == 4 and engine["steps"] > 0
    assert engine["late_pruned"] + engine["refilled"] <= engine["spilled"]


def records():
    def rec(cached, syncs, cand):
        return dict(status="ok", cached=cached,
                    stats=dict(host_syncs=syncs, candidates=cand))
    return [rec(False, 10, 100), rec(False, 30, 300), rec(True, 30, 300),
            rec(True, 30, 300)]


@pytest.mark.parametrize("name,value", [
    ("host_syncs_per_query", 20.0),
    ("candidates_per_query", 200.0),
    ("spill_mb_per_query", 3.0),
    ("device_idle_share", 75.0),
    ("expand_roofline", 100.0 * 10 * 4 * (4096 * 128 + 4096 + 64 * 258)
     / 819e9 / 2.0),
])
def test_metric_readers(name, value):
    ctx = dict(cell=small_cell(), config=small_cell()["config"],
               records=records(), peaks={"hbm_bytes_per_s": 819e9},
               counters={"vpq_spill_bytes_total": 5e6,
                         "vpq_refill_bytes_total": 1e6,
                         "engine_steps_total": 10},
               trace=dict(busy_s=2.0, window_s=8.0), window_s=8.0)
    ctx["config"]["n"] = 4096
    assert harness.metric_reader(name)(ctx) == pytest.approx(value)


def test_readers_read_nothing_without_their_source():
    ctx = dict(cell=small_cell(), config=small_cell()["config"], records=[],
               peaks=None, counters={}, trace=None, window_s=1.0)
    for m in harness.load_benchmark()["per_layer"]:
        assert harness.metric_reader(m["name"])(ctx) is None
