"""Maximum-weight clique via the succinct per-subgraph API (paper Table 1 /
Listing-1 style) — exercises from_pointwise end to end."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitset, weighted_clique
from repro.core.api import NEG, from_pointwise
from repro.core.engine import Engine, EngineConfig
from repro.core.weighted_clique import (brute_force_max_weight_clique,
                                        contraction_chunks,
                                        make_weighted_clique_computation,
                                        weight_table, weighted_counts)
from repro.data.synthetic_graphs import densifying_graph
from repro.obs import Observability
from repro.service import DiscoveryRequest, DiscoveryService
from repro.service.api import GraphRegistry, compile_request


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


# ------------------------------- batched MXU child scoring (exact integers)
def _pointwise_reference(comp, n):
    """The scalar per-state scorers weighted clique had before child
    scoring became one matrix product, through ``from_pointwise``'s scalar
    path; the rest of ``comp`` wrapped as scalar functions."""
    w = bitset.num_words(n)

    def set_weight(bits, wt):
        return jnp.sum(jnp.where(bitset.to_bool(bits, n), wt, 0))

    def expandable(s, a, t):
        return bitset.get_bit(bitset.to_u32(s[w:2 * w])[None],
                              jnp.asarray([a]))[0]

    def child_priority(s, a, t):
        new_p = bitset.to_u32(s[w:2 * w]) & t["ext"][a]
        return s[2 * w] + t["w"][a] + set_weight(new_p, t["w"])

    one = lambda f: lambda s, t: f(s[None], t)[0]        # noqa: E731
    return from_pointwise(
        name="weighted-clique-pointwise", state_width=comp.state_width,
        num_actions=n, init_frontier=comp.init_frontier,
        expandable=expandable, child_priority=child_priority,
        child_ub=child_priority,
        materialize_one=lambda s, a, t: comp.materialize(s[None], a[None],
                                                         t)[0],
        relevant=lambda s, t: jnp.bool_(True),
        result_key_one=one(comp.result_key),
        upper_bound_one=one(comp.upper_bound),
        describe=comp.describe, tables=comp.tables)


def _weighting(kind, g, seed=5):
    rng = np.random.default_rng(seed)
    if kind == "up_to_1000":
        return rng.integers(1, 1001, g.n)
    # weights across all four bytes, digits of 255 among them, on the two
    # vertices the most candidate sets hold (most neighbours below them);
    # the sum is just under 2**30
    wts = rng.integers(1, 2 ** 30 // (2 * g.n), g.n)
    hubs = np.argsort([np.sum(g.neighbors(u) < u) for u in range(g.n)])
    wts[hubs[-2]] = 2 ** 24 - 1
    wts[hubs[-1]] += 2 ** 30 - 1 - int(wts.sum())
    assert int(wts.sum()) == 2 ** 30 - 1 and wts[hubs[-1]] >= 2 ** 24
    return wts


def _engine_states(g, count, steps=3):
    """Live pool rows after the first super-steps of an engine on ``g``
    under weights in [1, 1000], and eight of their cliques as leaves:
    ``P`` empty, ``w(P)`` 0."""
    comp = make_weighted_clique_computation(g, _weighting("up_to_1000", g))
    eng = Engine(comp, EngineConfig(k=4, batch=8, pool_capacity=256,
                                    max_steps=50))
    st = eng.start()
    for _ in range(steps):
        eng.step(st)
    w = bitset.num_words(g.n)
    live = np.asarray(st.pool_states)[np.asarray(st.pool_prio) > int(NEG)]
    assert len(live) >= count
    leaves = live[:8].copy()
    leaves[:, w:2 * w] = 0
    leaves[:, 2 * w + 1] = 0
    return jnp.asarray(np.concatenate([live[:count - 8], leaves]))


@pytest.mark.parametrize("n", [64, 77])
@pytest.mark.parametrize("kind", ["up_to_1000", "four_bytes"])
def test_batched_scoring_matches_pointwise(kind, n):
    """The matrix-product scorer returns the scalar scorers' ``prio`` and
    ``ub`` grids bit for bit, on states from real engine steps."""
    g = densifying_graph(n, 6 * n, seed=2)
    comp = make_weighted_clique_computation(g, _weighting(kind, g))
    ref = _pointwise_reference(comp, n)
    states = _engine_states(g, 32)
    got = jax.jit(comp.score_children)(states, comp.tables)
    want = jax.jit(ref.score_children)(states, comp.tables)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(got[0]) > int(NEG)).sum() > 0


@pytest.mark.parametrize("kind", ["up_to_1000", "four_bytes"])
def test_engine_search_matches_pointwise(kind):
    """The same search, step for step: candidates, host syncs and top-k
    equal to an engine on the scalar scorers."""
    g = densifying_graph(50, 180, seed=3)
    comp = make_weighted_clique_computation(g, _weighting(kind, g))
    cfg = EngineConfig(k=3, batch=8, pool_capacity=128, max_steps=50000)
    got = Engine(comp, cfg).run()
    want = Engine(_pointwise_reference(comp, g.n), cfg).run()
    np.testing.assert_array_equal(got.result_keys, want.result_keys)
    np.testing.assert_array_equal(got.result_states, want.result_states)
    assert (got.steps, got.candidates, got.host_syncs) == \
        (want.steps, want.candidates, want.host_syncs)


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


def test_served_scoring_is_one_matmul():
    """The served scorer contracts with ``dot_general`` and never builds
    the ``[B, N, N]`` select-sum grid."""
    b, n = 64, 96
    g = densifying_graph(n, 6 * n, seed=1)
    reg = GraphRegistry()
    reg.register("g", g)
    comp = compile_request(DiscoveryRequest(
        graph="g", workload="weighted-clique", k=2,
        weights=tuple(range(1, n + 1))), reg).comp
    states = jnp.zeros((b, comp.state_width), jnp.int32)
    jaxpr = jax.make_jaxpr(comp.score_children)(states, comp.tables).jaxpr
    eqns = list(_all_eqns(jaxpr))
    assert sum(e.primitive.name == "dot_general" for e in eqns) == 1
    biggest = max(int(np.prod(v.aval.shape)) for e in eqns
                  for v in e.outvars)
    assert biggest < b * n * n, biggest


@pytest.mark.parametrize("n_bits", [32, 4096, 65792, 65793, 200000])
def test_contraction_chunks_stay_f32_exact(n_bits):
    """Each chunk of the contraction keeps every float32 partial sum of
    8-bit digits below 2**24; a single chunk up to 65,792 vertices."""
    words = bitset.num_words(n_bits)
    chunks = contraction_chunks(words)
    assert chunks[0][0] == 0 and chunks[-1][1] == words
    assert all(a[1] == b[0] for a, b in zip(chunks, chunks[1:]))
    assert all((hi - lo) * 32 * 255 < 2 ** 24 for lo, hi in chunks)
    assert len(chunks) == (1 if n_bits <= 65792 else -(-words // 2056))


def test_chunked_contraction_is_exact(monkeypatch):
    """With a small exact range the contraction splits into chunks, and
    their int32 sum is still the exact weighted popcount."""
    monkeypatch.setattr(weighted_clique, "_F32_EXACT", 2 ** 14)
    n = 150
    assert len(contraction_chunks(bitset.num_words(n))) == 3
    rng = np.random.default_rng(4)
    p = rng.random((16, n)) < 0.5
    ext = rng.random((n, n)) < 0.3
    wts = _weighting("four_bytes", densifying_graph(n, 6 * n, seed=4))
    got = weighted_counts(jnp.asarray(bitset.from_bool(p)),
                          jnp.asarray(bitset.from_bool(ext)),
                          jnp.asarray(wts, jnp.int32))
    want = (p[:, None, :] & ext[None, :, :]).astype(np.int64) @ wts
    np.testing.assert_array_equal(np.asarray(got), want)


def test_from_pointwise_takes_scalar_or_batched_scorers():
    """A batched ``score_children`` replaces the three scalar scorers; a
    mix of the two is refused."""
    g = densifying_graph(20, 40, seed=0)
    comp = make_weighted_clique_computation(g, np.ones(g.n, int))
    ref = _pointwise_reference(comp, g.n)
    rest = dict(materialize_one=lambda s, a, t: s,
                relevant=lambda s, t: True,
                result_key_one=lambda s, t: s[0],
                upper_bound_one=lambda s, t: s[0])
    base = dict(name="x", state_width=comp.state_width, num_actions=g.n,
                init_frontier=comp.init_frontier, **rest)
    assert from_pointwise(**base, score_children=comp.score_children
                          ).score_children is comp.score_children
    for bad in (dict(),
                dict(expandable=lambda s, a, t: True),
                dict(expandable=lambda s, a, t: True,
                     child_priority=lambda s, a, t: 0,
                     child_ub=lambda s, a, t: 0,
                     score_children=ref.score_children)):
        with pytest.raises(ValueError, match="score_children"):
            from_pointwise(**base, **bad)
