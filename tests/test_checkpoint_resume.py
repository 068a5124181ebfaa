"""Durable discovery runs (DESIGN.md §15): in-process resume parity,
service-layer checkpoint policy, and the serve-loop ``--resume`` path.

The crash-injection suite (``test_fault_injection.py``) proves the
contract across real SIGKILLs; this file carries the cheaper in-process
halves:

* resuming an *intermediate* committed step and continuing produces a
  byte-identical result to the uninterrupted run (engine + 2-shard);
* the checkpoint knobs are excluded from the result-cache key but
  included in the engine-reuse key — both directions, mirroring the
  ``sync_every`` discipline in ``test_stale_bound.py``;
* a resumed query honors the absolute ``step_budget`` exactly and never
  double-counts its pre-crash steps into ``engine_steps_total``;
* ``launch.serve`` restarted with ``resume=True`` finishes a truncated
  checkpointed request with the uninterrupted answer, beating the
  heartbeat as it goes.
"""
import dataclasses
import io
import json
import os

import numpy as np
import pytest

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.core.clique import make_clique_computation
from repro.core.engine import Engine, EngineConfig
from repro.data.synthetic_graphs import densifying_graph
from repro.distributed import ShardedEngine
from repro.obs import Observability
from repro.service import (DiscoveryRequest, DiscoveryService,
                           ValidationError)


def _require_devices(n: int) -> None:
    if len(jax.devices()) < n:
        pytest.skip(f"needs >= {n} devices (force host devices with "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count={n})")


def _assert_result_parity(a, b, ctx=""):
    np.testing.assert_array_equal(a.result_keys, b.result_keys, err_msg=ctx)
    np.testing.assert_array_equal(a.result_states, b.result_states,
                                  err_msg=ctx)
    assert (a.steps, a.candidates, a.expanded, a.pruned, a.spilled,
            a.refilled, a.late_pruned, a.syncs, a.host_syncs) == \
           (b.steps, b.candidates, b.expanded, b.pruned, b.spilled,
            b.refilled, b.late_pruned, b.syncs, b.host_syncs), ctx


# ------------------------------------------------------ engine-level parity
@pytest.mark.parametrize("spill,T", [("host", 1), ("disk", 4)])
def test_resume_intermediate_step_matches_uninterrupted(tmp_path, spill, T):
    """Resume from a NON-final committed step (not the newest) and run to
    completion: byte-identical results and counters."""
    g = densifying_graph(72, 600, seed=2)
    comp = make_clique_computation(g)
    cfg = EngineConfig(k=3, batch=4, pool_capacity=48, max_steps=50_000,
                       spill=spill, spill_dir=str(tmp_path / "s1"),
                       steps_per_sync=T)
    oracle = Engine(comp, cfg).run()
    assert oracle.steps > 20, "workload too short to leave mid-run ckpts"

    ck = str(tmp_path / "ckpt")
    ckcfg = dataclasses.replace(cfg, spill_dir=str(tmp_path / "s2"),
                                checkpoint_every=8, checkpoint_dir=ck)
    durable = Engine(comp, ckcfg).run()
    _assert_result_parity(oracle, durable, "checkpointing perturbed run")

    mgr = CheckpointManager(ck)
    committed = mgr.committed_steps()
    assert len(committed) >= 2
    mid = committed[0]                       # oldest retained, < final
    assert mid < oracle.steps
    reng = Engine(comp, dataclasses.replace(
        ckcfg, spill_dir=str(tmp_path / "s3")))
    st = reng.resume(mgr, step=mid)
    assert st.steps == mid
    while not st.done and st.steps < ckcfg.max_steps:
        reng.step(st, max_inner=ckcfg.max_steps - st.steps)
    _assert_result_parity(oracle, reng.finalize(st),
                          f"resume from step {mid} diverged")


def test_sharded_resume_matches_uninterrupted(tmp_path):
    """2-shard resume: per-shard VPQs and pool_occupancy round-trip."""
    _require_devices(2)
    g = densifying_graph(72, 600, seed=4)
    comp = make_clique_computation(g)
    cfg = EngineConfig(k=3, batch=4, pool_capacity=48, max_steps=50_000,
                       shards=2, sync_every=2, steps_per_sync=2,
                       spill="disk", spill_dir=str(tmp_path / "s1"))
    oracle = ShardedEngine(comp, cfg).run()

    ck = str(tmp_path / "ckpt")
    ckcfg = dataclasses.replace(cfg, spill_dir=str(tmp_path / "s2"),
                                checkpoint_every=8, checkpoint_dir=ck)
    ShardedEngine(comp, ckcfg).run()
    mgr = CheckpointManager(ck)
    mid = mgr.committed_steps()[0]
    reng = ShardedEngine(comp, dataclasses.replace(
        ckcfg, spill_dir=str(tmp_path / "s3")))
    st = reng.resume(mgr, step=mid)
    while not st.done and st.steps < ckcfg.max_steps:
        reng.step(st, max_inner=ckcfg.max_steps - st.steps)
    res = reng.finalize(st)
    _assert_result_parity(oracle, res, f"sharded resume from {mid}")
    assert res.rebalanced == oracle.rebalanced


# --------------------------------------------------------------- cache keys
def test_checkpoint_knobs_excluded_from_result_cache_key(tmp_path):
    """Direction 1: checkpointing is a pure observer, so checkpointed,
    resumed, and plain runs of one query share a result-cache entry."""
    r1 = DiscoveryRequest(graph="g", workload="clique", k=3)
    r2 = dataclasses.replace(r1, checkpoint_every=16,
                             checkpoint_dir=str(tmp_path / "ck"),
                             resume=True)
    assert r1.canonical_spec() == r2.canonical_spec()
    svc = DiscoveryService()
    svc.register_graph("g", densifying_graph(48, 160, seed=3))
    first = svc.query(DiscoveryRequest(graph="g", workload="clique", k=3))
    hit = svc.query(DiscoveryRequest(
        graph="g", workload="clique", k=3, checkpoint_every=8,
        checkpoint_dir=str(tmp_path / "ck2")))
    assert first.status == "ok" and hit.status == "ok", \
        (first.error, hit.error)
    assert not first.cached and hit.cached
    assert first.result_keys == hit.result_keys


def test_checkpoint_knobs_included_in_engine_reuse_key(tmp_path):
    """Direction 2: the checkpoint policy rides EngineConfig, so requests
    with different policies must NOT share a compiled engine."""
    svc = DiscoveryService()
    svc.register_graph("g", densifying_graph(48, 160, seed=3))
    base = dict(graph="g", workload="clique", k=3, use_cache=False)
    svc.query(DiscoveryRequest(**base))
    assert len(svc._engines) == 1
    svc.query(DiscoveryRequest(**base))            # same policy: reused
    assert len(svc._engines) == 1
    svc.query(DiscoveryRequest(**base, checkpoint_every=8,
                               checkpoint_dir=str(tmp_path / "ck")))
    assert len(svc._engines) == 2                  # new policy: new engine
    svc.query(DiscoveryRequest(**base, checkpoint_every=8,
                               checkpoint_dir=str(tmp_path / "ck")))
    assert len(svc._engines) == 2


# ------------------------------------------------------------ service layer
def test_resumed_query_honors_budget_and_step_accounting(tmp_path):
    """A truncated checkpointed query resumed with a larger budget stops
    at the ABSOLUTE budget (pre-crash steps count), reproduces the
    uninterrupted truncation byte-for-byte, and adds only its delta to
    ``engine_steps_total``."""
    g = densifying_graph(64, 256, seed=5)
    ck = str(tmp_path / "ck")
    base = dict(graph="g", workload="clique", k=3, batch=8,
                pool_capacity=64, use_cache=False)

    oracle_svc = DiscoveryService()
    oracle_svc.register_graph("g", g)
    oracle = oracle_svc.query(DiscoveryRequest(**base, step_budget=14))
    assert oracle.terminated == "step_budget"
    assert oracle.stats["steps"] == 14

    svc = DiscoveryService()
    svc.register_graph("g", g)
    part = svc.query(DiscoveryRequest(**base, step_budget=6,
                                      checkpoint_every=4,
                                      checkpoint_dir=ck))
    assert part.terminated == "step_budget" and part.stats["steps"] == 6
    assert CheckpointManager(ck).latest_step() == 6   # terminal ckpt
    assert svc.engine_steps_total == 6

    svc2 = DiscoveryService()
    svc2.register_graph("g", g)
    done = svc2.query(DiscoveryRequest(**base, step_budget=14,
                                       checkpoint_every=4,
                                       checkpoint_dir=ck, resume=True))
    assert done.terminated == "step_budget"
    assert done.stats["steps"] == 14        # absolute, not 6 + 14
    assert svc2.engine_steps_total == 14 - 6, \
        "resumed query double-counted its pre-crash steps"
    assert done.result_keys == oracle.result_keys
    assert done.results == oracle.results
    assert "straggler_steps" in done.stats


def test_resume_through_an_engine_another_weighting_built(tmp_path):
    """A checkpointed weighted-clique query resumed through a shared
    engine, one that another weighting built, continues byte-identically;
    a task of that other weighting in the same batch keeps its own
    tables (weights are per-query tables, not part of the engine key)."""
    g = densifying_graph(64, 256, seed=5)
    rng = np.random.default_rng(11)
    w1, w2 = (tuple(int(x) for x in rng.integers(1, 60, g.n))
              for _ in range(2))
    ck = str(tmp_path / "ck")
    base = dict(graph="g", workload="weighted-clique", k=3, batch=8,
                pool_capacity=64, use_cache=False)

    def service():
        svc = DiscoveryService(observability=Observability())
        svc.register_graph("g", g)
        return svc

    def answer(resp):
        assert resp.status == "ok", resp.error
        stats = dict(resp.stats)
        stats.pop("straggler_steps")
        return json.dumps(dict(keys=resp.result_keys, results=resp.results,
                               stats=stats, terminated=resp.terminated))

    oracle1 = service().query(DiscoveryRequest(**base, weights=w1))
    oracle2 = service().query(DiscoveryRequest(**base, weights=w2))
    # cut early, while the resumed search still has most of its
    # candidates to score with the weights
    part = service().query(DiscoveryRequest(
        **base, weights=w1, step_budget=2, checkpoint_every=2,
        checkpoint_dir=ck))
    assert part.stats["candidates"] < oracle1.stats["candidates"]
    assert part.terminated == "step_budget"

    # the restart reads the checkpoint and writes none (checkpoint_every
    # 0), so every request below has one engine key
    svc = service()
    svc.query(DiscoveryRequest(**base, weights=w2, checkpoint_dir=ck))
    resumed, other = svc.serve([
        DiscoveryRequest(**base, weights=w1, checkpoint_dir=ck,
                         resume=True),
        DiscoveryRequest(**base, weights=w2, checkpoint_dir=ck)])
    assert svc.obs.metrics.get("service_engine_builds_total").value == 1
    assert answer(resumed) == answer(oracle1)
    assert answer(other) == answer(oracle2)
    assert svc.engine_steps_total == \
        2 * oracle2.stats["steps"] + oracle1.stats["steps"] - 2


def test_resume_with_empty_checkpoint_dir_starts_fresh(tmp_path):
    """resume=True with no committed step is a fresh start, not an error
    (the crash-before-first-commit restart path)."""
    svc = DiscoveryService()
    svc.register_graph("g", densifying_graph(48, 160, seed=3))
    resp = svc.query(DiscoveryRequest(
        graph="g", workload="clique", k=3, use_cache=False,
        checkpoint_every=8, checkpoint_dir=str(tmp_path / "empty"),
        resume=True))
    assert resp.status == "ok", resp.error
    assert resp.terminated == "complete"


def test_checkpoint_request_validation():
    with pytest.raises(ValidationError, match="checkpoint_dir"):
        DiscoveryRequest(graph="g", workload="clique", k=1,
                         checkpoint_every=8).validate(None)
    with pytest.raises(ValidationError, match="checkpoint_dir"):
        DiscoveryRequest(graph="g", workload="clique", k=1,
                         resume=True).validate(None)
    with pytest.raises(ValidationError, match="engine workloads"):
        DiscoveryRequest(graph="g", workload="pattern", k=1,
                         checkpoint_every=8,
                         checkpoint_dir="/tmp/x").validate(None)
    req = DiscoveryRequest.from_dict(dict(
        graph="g", workload="clique", k=1, checkpoint_every="8",
        checkpoint_dir="/tmp/x", resume="true"))
    assert req.checkpoint_every == 8 and req.resume is True


# ------------------------------------------------------------- serve loop
def test_serve_resume_finishes_truncated_request(tmp_path):
    """Kill-and-resume through the serving driver: a checkpointed request
    truncated in one serve process finishes byte-identically in a second
    process started with ``--resume``, and the heartbeat file advances."""
    from repro.launch.serve import serve_discovery
    from repro.runtime.fault_tolerance import Heartbeat

    ck = str(tmp_path / "ck")
    hb = str(tmp_path / "hb")
    base = dict(graph="demo-social", workload="clique", k=3, batch=8,
                pool_capacity=64, use_cache=False, request_id="q1")

    out = io.StringIO()
    serve_discovery(lines=[json.dumps(dict(base, step_budget=400))],
                    out=out)
    oracle = json.loads(out.getvalue().splitlines()[0])
    assert oracle["status"] == "ok"

    out = io.StringIO()
    serve_discovery(
        lines=[json.dumps(dict(base, step_budget=8, checkpoint_every=4,
                               checkpoint_dir=ck))],
        out=out, heartbeat=hb)
    first = json.loads(out.getvalue().splitlines()[0])
    assert first["terminated"] == "step_budget"
    assert not Heartbeat.is_stale(hb, timeout=120)

    # "restart" with --resume: same request line, full budget
    out = io.StringIO()
    serve_discovery(
        lines=[json.dumps(dict(base, step_budget=400, checkpoint_every=4,
                               checkpoint_dir=ck))],
        out=out, resume=True, heartbeat=hb)
    resumed = json.loads(out.getvalue().splitlines()[0])
    assert resumed["status"] == "ok", resumed.get("error")
    assert resumed["result_keys"] == oracle["result_keys"]
    assert resumed["stats"]["steps"] == oracle["stats"]["steps"]
    assert not [d for d in os.listdir(ck) if d.endswith(".tmp")]
