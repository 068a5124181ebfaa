"""Subgraph isomorphism + pattern mining vs exact oracles."""
import numpy as np
import pytest

from repro.core.aggregate import (arabesque_style_mining,
                                  max_support_of_size,
                                  topk_frequent_patterns)
from repro.core.engine import Engine, EngineConfig
from repro.core.exhaustive import brute_force_iso, pattern_support_oracle
from repro.core.graph import GraphStore
from repro.core.iso import build_iso_index, make_iso_computation
from repro.core.patterns import (code_vertex_labels, is_min_code,
                                 min_dfs_code)
from repro.data.synthetic_graphs import labeled_graph


QUERIES = [
    ([(0, 1)], [0, 1]),                       # edge
    ([(0, 1), (1, 2)], [0, 1, 2]),            # path
    ([(0, 1), (1, 2), (0, 2)], [1, 1, 1]),    # triangle
    ([(0, 1), (1, 2), (2, 3)], [0, 1, 0, 2]),  # labeled path-4
]


@pytest.mark.parametrize("q_edges,q_labels", QUERIES)
@pytest.mark.parametrize("k", [1, 3])
def test_iso_topk_matches_oracle(q_edges, q_labels, k):
    g = labeled_graph(n=120, m=420, n_labels=3, seed=2)
    oracle = brute_force_iso(g, q_edges, q_labels, induced=True, k=k)
    index = build_iso_index(g, max_hops=3)
    comp = make_iso_computation(g, q_edges, q_labels, index)
    res = Engine(comp, EngineConfig(k=k, batch=64, pool_capacity=8192,
                                    max_steps=50000)).run()
    got = [int(x) for x in res.result_keys if x > -2 ** 31 + 1]
    want = [s for s, _ in oracle]
    assert got == want


def test_iso_index_upper_bound_sound():
    """index[v,l,h] >= degree of any label-l vertex exactly h hops from v."""
    g = labeled_graph(n=80, m=240, n_labels=3, seed=5)
    index = build_iso_index(g, max_hops=3)
    for v in range(0, g.n, 7):
        hops = g.bfs_hops(v, 3)
        for u in range(g.n):
            h = hops[u]
            if 1 <= h <= 3:
                assert index[v, g.labels[u], h - 1] >= g.degrees[u]


def _dense_iso_index(g, max_hops, predicate=None):
    """The dense-reachability form of build_iso_index: [N, N] boolean
    hop matrices, one matmul per hop (fits only small graphs)."""
    n = g.n
    ea = g.edge_array
    if predicate is not None and predicate.edge_any_of is not None:
        ea = ea[predicate.edge_mask_csr(g)]
    adj = np.zeros((n, n), np.int64)
    adj[ea[:, 0], ea[:, 1]] = 1
    index = np.zeros((n, g.n_labels, max_hops), np.int32)
    reached = frontier = np.eye(n, dtype=np.int64)
    for h in range(max_hops):
        level = ((frontier @ adj > 0) & (reached == 0)).astype(np.int64)
        reached = reached | level
        frontier = level
        for lab in range(g.n_labels):
            degl = np.where(g.labels == lab, g.degrees, 0)
            index[:, lab, h] = (level * degl[None, :]).max(axis=1)
    return index


@pytest.mark.parametrize("max_hops", [1, 2, 3])
@pytest.mark.parametrize("edge_any_of", [None, [0], [1]])
def test_iso_index_matches_dense_reachability(max_hops, edge_any_of):
    """The sparse frontier expansion gives exactly the dense-matmul index,
    also on a type-restricted adjacency (degrees stay full-graph)."""
    from repro.core.labels import LabelPredicate
    from repro.data.synthetic_graphs import attributed_graph
    g = attributed_graph(90, 300, n_labels=4, n_edge_labels=2, seed=3)
    pred = (LabelPredicate.from_spec({"edge_any_of": edge_any_of})
            if edge_any_of else None)
    np.testing.assert_array_equal(
        build_iso_index(g, max_hops, predicate=pred),
        _dense_iso_index(g, max_hops, pred))


def test_pattern_mining_paper_example():
    """The paper's Figure 1b/5 worked example: p4=(b-b-b path), support 3."""
    edges = [(0, 1), (1, 2), (1, 3), (2, 3), (4, 3)]
    labels = [0, 1, 1, 1, 0]
    g = GraphStore.from_edges(5, np.array(edges), labels=np.array(labels))
    res = topk_frequent_patterns(g, m_edges=2, k=1)
    sup, code = res.patterns[0]
    assert sup == 3
    assert code == ((0, 1, 1, 1), (1, 2, 1, 1))
    # 1-edge supports match the paper: f(a-b)=2, f(b-b)=3
    assert pattern_support_oracle(g, [(0, 1)], [0, 1]) == 2
    assert pattern_support_oracle(g, [(0, 1)], [1, 1]) == 3


@pytest.mark.parametrize("m_edges", [2, 3])
def test_pattern_supports_match_oracle(m_edges):
    g = labeled_graph(n=60, m=150, n_labels=3, seed=5)
    res = topk_frequent_patterns(g, m_edges=m_edges, k=3)
    assert res.patterns
    for sup, code in res.patterns:
        vl = code_vertex_labels(code)
        pe = [(i, j) for i, j, _, _ in code]
        assert pattern_support_oracle(g, pe, vl) == sup


def test_nuri_vs_arabesque_threshold_baseline():
    """Abq at T=µ finds the same top pattern; at T=µ/3 it explores more
    candidates (paper §6.3)."""
    g = labeled_graph(n=60, m=180, n_labels=4, seed=8)
    mu = max_support_of_size(g, 3)
    nuri = topk_frequent_patterns(g, m_edges=3, k=1)
    at_mu = arabesque_style_mining(g, m_edges=3, threshold=mu)
    at_mu3 = arabesque_style_mining(g, m_edges=3, threshold=max(1, mu // 3))
    assert at_mu.patterns[0][0] == nuri.patterns[0][0] == mu
    assert at_mu3.candidates >= at_mu.candidates
    assert nuri.patterns[0][0] == at_mu3.patterns[0][0]


def test_min_code_canonical():
    # P3 star form is non-minimal; path form is minimal
    assert not is_min_code(((0, 1, 1, 1), (0, 2, 1, 1)))
    assert is_min_code(((0, 1, 1, 1), (1, 2, 1, 1)))
    # triangle
    assert is_min_code(((0, 1, 0, 0), (1, 2, 0, 0), (2, 0, 0, 0)))
