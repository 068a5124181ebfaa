"""The check fails the control and each fault the timed path can have.

Each test drives a whole run at a small size on the CPU (the look for a
chip skipped), with the timed path broken underneath, and sees
``correct`` come out false."""
import dataclasses
import threading
import time

import jax.numpy as jnp

from bench import control, harness
from bench.test_bench_run import small_cell

SEED = 3 * 2 ** 30 + 1


def run(**kw):
    return harness.run_cell(small_cell(n=512), SEED, 0.5, False,
                            time.perf_counter(), require_tpu=False, **kw)


def test_control_reads_not_correct():
    # every search is cut short; at this size the best-first order has
    # already found the answers, so the cut shows as ``incomplete``
    res = run(overrides=dict(step_budget=control.CONTROL_STEPS))
    assert res["correct"] is False
    assert res["limits"]["incomplete"]["value"] == res["attempted"] > 0


def test_answer_altered_where_produced(monkeypatch):
    from repro.service import scheduler
    finalize = scheduler.EngineQueryTask.finalize

    def altered(self):
        p = finalize(self)
        return dict(p, result_keys=[p["result_keys"][0] + 1] +
                    p["result_keys"][1:])

    monkeypatch.setattr(scheduler.EngineQueryTask, "finalize", altered)
    res = run()
    assert res["correct"] is False
    assert res["limits"]["mismatched"]["value"] == res["attempted"]


def test_half_of_the_batch_left_out(monkeypatch):
    from repro.core.api import NEG
    from repro.service import scheduler
    compile_request = scheduler.compile_request

    def halved(*a, **kw):
        cq = compile_request(*a, **kw)
        score = cq.comp.score_children

        def score_half(states, tables):
            prio, ub = score(states, tables)
            keep = (jnp.arange(states.shape[0]) < states.shape[0] // 2)
            return (jnp.where(keep[:, None], prio, NEG),
                    jnp.where(keep[:, None], ub, NEG))

        return dataclasses.replace(cq, comp=dataclasses.replace(
            cq.comp, score_children=score_half))

    monkeypatch.setattr(scheduler, "compile_request", halved)
    res = run()
    assert res["correct"] is False
    assert res["limits"]["mismatched"]["value"] > 0


def test_step_that_leaves_its_state_unchanged(monkeypatch):
    from repro.core.engine import Engine
    release = threading.Event()

    def stalled(self, st, max_inner=None):
        # set-up runs as it should; in the window no step makes progress
        if threading.current_thread().name != "bench-window":
            return step(self, st, max_inner)
        if release.wait(0.01):
            raise RuntimeError("released")
        return st

    step = Engine.step

    monkeypatch.setattr(Engine, "step", stalled)
    try:
        res = run(grace_s=1.0)
    finally:
        release.set()
    assert res["correct"] is False
    assert res["limits"]["unanswered"]["value"] == 4
