"""Maximum-weight clique via the succinct per-subgraph API (paper Table 1 /
Listing-1 style) — exercises from_pointwise end to end."""
import json

import numpy as np
import pytest

from repro.core.engine import Engine, EngineConfig
from repro.core.weighted_clique import (brute_force_max_weight_clique,
                                        make_weighted_clique_computation,
                                        weight_table)
from repro.data.synthetic_graphs import densifying_graph
from repro.obs import Observability
from repro.service import DiscoveryRequest, DiscoveryService


@pytest.mark.parametrize("seed", [0, 3])
def test_weighted_clique_matches_bruteforce(seed):
    g = densifying_graph(50, 180, seed=seed)
    weights = np.random.default_rng(seed).integers(1, 20, g.n)
    want_w, want_members = brute_force_max_weight_clique(g, weights)
    comp = make_weighted_clique_computation(g, weights)
    res = Engine(comp, EngineConfig(k=1, batch=16, pool_capacity=4096,
                                    max_steps=50000)).run()
    assert int(res.result_keys[0]) == want_w
    members = comp.describe(res.result_states[0])
    assert sum(int(weights[v]) for v in members) == want_w
    for i, u in enumerate(members):
        for v in members[i + 1:]:
            assert g.has_edge(u, v)


# ------------------------------------- one engine across weightings (§9.4)
def _weightings(n, count, seed=7):
    rng = np.random.default_rng(seed)
    return [tuple(int(x) for x in rng.integers(1, 50, n))
            for _ in range(count)]


def _answer(resp):
    """Everything a response says of its search, byte for byte: the
    answer and the step, candidate and host-sync counts."""
    assert resp.status == "ok", resp.error
    return json.dumps(dict(keys=resp.result_keys, results=resp.results,
                           steps=resp.stats["steps"],
                           candidates=resp.stats["candidates"],
                           host_syncs=resp.stats["host_syncs"]))


def _fresh_answer(g, req):
    """The same request on a service of its own: a freshly built engine
    whose default tables are this request's weights."""
    svc = DiscoveryService()
    svc.register_graph("g", g)
    return _answer(svc.query(req))


@pytest.mark.parametrize("batches", ["one_batch", "across_batches"])
def test_one_engine_serves_every_weighting(batches):
    g = densifying_graph(50, 180, seed=3)
    ws = _weightings(g.n, 3)
    reqs = [DiscoveryRequest(graph="g", workload="weighted-clique", k=2,
                             weights=w, batch=8, pool_capacity=64,
                             observe=True) for w in ws]
    svc = DiscoveryService(observability=Observability())
    svc.register_graph("g", g)
    try:
        if batches == "one_batch":
            resps = svc.serve(reqs)
        else:
            resps = svc.serve(reqs[:1])
            traced = svc.obs.metrics.get("jax_trace_seconds_total").value
            resps += svc.serve(reqs[1:])
            # the later weightings ran the first one's programs: no trace
            assert svc.obs.metrics.get(
                "jax_trace_seconds_total").value == traced
    finally:
        svc.close()
    assert svc.obs.metrics.get("service_engine_builds_total").value == 1
    for w, req, resp in zip(ws, reqs, resps):
        assert _answer(resp) == _fresh_answer(g, req)
        best, members = brute_force_max_weight_clique(g, np.asarray(w))
        assert resp.result_keys[0] == best
        assert sum(w[v] for v in resp.results[0]) == best


def test_engine_start_takes_a_weighting():
    """Engine.start with another weighting's tables searches exactly as an
    engine built for that weighting."""
    g = densifying_graph(40, 150, seed=1)
    w1, w2 = (np.asarray(w) for w in _weightings(g.n, 2, seed=2))
    cfg = EngineConfig(k=3, batch=8, pool_capacity=64, max_steps=50000)
    eng = Engine(make_weighted_clique_computation(g, w1), cfg)
    st = eng.start(dict(eng.tables, w=weight_table(w2)))
    while not st.done:
        eng.step(st)
    got = eng.finalize(st)
    want = Engine(make_weighted_clique_computation(g, w2), cfg).run()
    np.testing.assert_array_equal(got.result_keys, want.result_keys)
    np.testing.assert_array_equal(got.result_states, want.result_states)
    assert (got.steps, got.candidates, got.host_syncs) == \
        (want.steps, want.candidates, want.host_syncs)
    assert eng._step._cache_size() == 1     # one program for both


def test_weighting_beyond_the_priority_keys_is_rejected():
    """A weighting whose sum overflows the int32 priority keys gets an
    error response, and the engine serves the next weighting."""
    g = densifying_graph(40, 150, seed=1)
    svc = DiscoveryService()
    svc.register_graph("g", g)
    ok, = _weightings(g.n, 1)
    bad = (2 ** 25,) * g.n
    resps = svc.serve([DiscoveryRequest(graph="g", workload="weighted-clique",
                                        k=2, weights=w) for w in (bad, ok)])
    assert resps[0].status == "error" and "2**30" in resps[0].error
    assert resps[1].status == "ok", resps[1].error
