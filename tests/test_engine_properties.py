"""Hypothesis property tests on the system's invariants."""
import os

import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import bitset
from repro.core.clique import make_clique_computation
from repro.core.graph import GraphStore
from repro.core.patterns import code_key, is_min_code, min_dfs_code
from repro.core.vpq import NEG, VirtualPriorityQueue

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


# ---------------------------------------------------------------- bitsets
@given(st.lists(st.integers(0, 199), max_size=64), st.just(200))
def test_bitset_roundtrip(indices, n):
    packed = bitset.from_indices(indices, n)
    dense = np.asarray(bitset.to_bool(jnp.asarray(packed)[None], n))[0]
    want = np.zeros(n, bool)
    want[list(set(indices))] = True
    np.testing.assert_array_equal(dense, want)
    assert int(bitset.popcount(jnp.asarray(packed)[None])[0]) == \
        len(set(indices))


@given(st.integers(1, 130))
def test_lt_mask_table(n):
    table = bitset.lt_mask_table(n)
    dense = np.asarray(bitset.to_bool(jnp.asarray(table), n))
    want = np.arange(n)[None, :] > np.arange(n)[:, None]
    np.testing.assert_array_equal(dense, want)


# ------------------------------------------------------------------- VPQ
@given(st.lists(st.integers(-10**6, 10**6), min_size=1, max_size=300),
       st.sampled_from(["host"]))
def test_vpq_pops_in_priority_order(prios, backend):
    vpq = VirtualPriorityQueue(state_width=2, backend=backend,
                               run_flush_size=32)
    prios = np.asarray(prios, np.int32)
    states = np.stack([prios, prios], 1).astype(np.int32)
    # push in several fragments → multiple runs
    for i in range(0, len(prios), 37):
        sl = slice(i, i + 37)
        vpq.maybe_push(states[sl], prios[sl], prios[sl])
    _, got, _ = vpq.pop_chunk(len(prios))
    np.testing.assert_array_equal(got, np.sort(prios)[::-1])
    assert len(vpq) == 0


@given(st.lists(st.tuples(st.integers(-1000, 1000), st.integers(-1000, 1000)),
                min_size=1, max_size=100))
def test_vpq_late_pruning_drops_dominated(entries):
    vpq = VirtualPriorityQueue(state_width=1, backend="host")
    prios = np.asarray([e[0] for e in entries], np.int32)
    ubs = np.asarray([e[1] for e in entries], np.int32)
    vpq.maybe_push(prios[:, None].copy(), prios, ubs)
    thr = 0
    _, got_p, got_u = vpq.pop_chunk(len(entries), min_ub=thr)
    assert (got_u >= thr).all()
    assert len(got_p) == int((ubs >= thr).sum())


# ------------------------------------------------------- engine invariants
@st.composite
def random_graph(draw):
    n = draw(st.integers(8, 40))
    m = draw(st.integers(n, 3 * n))
    rng = np.random.default_rng(draw(st.integers(0, 10**6)))
    edges = rng.integers(0, n, size=(m, 2))
    return GraphStore.from_edges(n, edges)


@given(random_graph())
def test_clique_ub_anti_monotone(g):
    """API contract: ub(child) <= ub(parent) and result_key <= ub."""
    comp = make_clique_computation(g)
    states, prio, ub = comp.init_frontier(comp.tables)
    rk = comp.result_key(states, comp.tables)
    assert bool(jnp.all(rk <= ub))
    child_prio, child_ub = comp.score_children(states, comp.tables)
    valid = child_prio > jnp.iinfo(jnp.int32).min
    # each child's ub <= its parent's ub
    bound = jnp.where(valid, child_ub, -10**9)
    assert bool(jnp.all(bound <= ub[:, None]))


@given(random_graph())
def test_clique_expansion_canonical(g):
    """Children only add vertices greater than every parent vertex."""
    comp = make_clique_computation(g)
    states, _, _ = comp.init_frontier(comp.tables)
    child_prio, _ = comp.score_children(states, comp.tables)
    valid = np.asarray(child_prio > jnp.iinfo(jnp.int32).min)
    for v in range(g.n):             # seed {v} may only expand to u > v
        assert not valid[v, :v + 1].any()


# ------------------------------------------------------------ DFS codes
@st.composite
def small_pattern(draw):
    nv = draw(st.integers(2, 5))
    labels = [draw(st.integers(0, 2)) for _ in range(nv)]
    edges = {(0, 1)}
    for v in range(2, nv):           # connected: attach each vertex
        u = draw(st.integers(0, v - 1))
        edges.add((u, v))
    extra = draw(st.integers(0, 2))
    for _ in range(extra):
        a = draw(st.integers(0, nv - 1))
        b = draw(st.integers(0, nv - 1))
        if a != b:
            edges.add((min(a, b), max(a, b)))
    return labels, sorted(edges)


@given(small_pattern(), st.integers(0, 10**6))
def test_min_dfs_code_relabel_invariant(pat, seed):
    """The canonical code is invariant under vertex relabeling."""
    labels, edges = pat
    nv = len(labels)
    code1 = min_dfs_code(labels, edges)
    perm = np.random.default_rng(seed).permutation(nv)
    labels2 = [0] * nv
    for v in range(nv):
        labels2[perm[v]] = labels[v]
    edges2 = [(int(perm[a]), int(perm[b])) for a, b in edges]
    code2 = min_dfs_code(labels2, edges2)
    assert code1 == code2
    assert is_min_code(code1)


# ----------------------------------------------- checkpoint round-trip
@given(st.integers(0, 10**6), st.integers(0, 12),
       st.sampled_from(["host", "disk"]), st.integers(1, 3))
def test_checkpoint_roundtrip_preserves_finalize(seed, steps, backend, T):
    """DESIGN.md §15 invariant: ``finalize(restore(snapshot(st)))`` equals
    ``finalize(st)`` for an arbitrary mid-run state — results, counters,
    and the *entire* remaining VPQ content byte-for-byte."""
    import tempfile
    from repro.checkpoint.manager import CheckpointManager
    from repro.core.engine import Engine, EngineConfig

    rng = np.random.default_rng(seed)
    n = int(rng.integers(12, 48))
    g = GraphStore.from_edges(
        n, rng.integers(0, n, size=(int(rng.integers(n, 4 * n)), 2)))
    with tempfile.TemporaryDirectory() as tmp:
        cfg = EngineConfig(k=3, batch=4, pool_capacity=16, spill=backend,
                           spill_dir=os.path.join(tmp, "spill"),
                           steps_per_sync=T)
        eng = Engine(make_clique_computation(g), cfg)
        st_live = eng.start()
        for _ in range(steps):
            if st_live.done:
                break
            eng.step(st_live)
        mgr = CheckpointManager(os.path.join(tmp, "ckpt"))
        eng.save_checkpoint(mgr, st_live, blocking=True)
        st_back = eng.resume(mgr)

        for name in ("steps", "candidates", "expanded", "pruned",
                     "refilled", "syncs", "host_syncs", "threshold",
                     "pool_occupancy", "done"):
            assert getattr(st_back, name) == getattr(st_live, name), name
        assert len(st_back.vpq) == len(st_live.vpq)
        # remaining VPQ drains identically (order and content)
        while len(st_live.vpq):
            s1, p1, u1 = st_live.vpq.pop_chunk(7)
            s2, p2, u2 = st_back.vpq.pop_chunk(7)
            np.testing.assert_array_equal(s1, s2)
            np.testing.assert_array_equal(p1, p2)
            np.testing.assert_array_equal(u1, u2)
        assert len(st_back.vpq) == 0

        r1, r2 = eng.finalize(st_live), eng.finalize(st_back)
        np.testing.assert_array_equal(r1.result_states, r2.result_states)
        np.testing.assert_array_equal(r1.result_keys, r2.result_keys)
        assert (r1.steps, r1.candidates, r1.expanded, r1.pruned) == \
            (r2.steps, r2.candidates, r2.expanded, r2.pruned)
