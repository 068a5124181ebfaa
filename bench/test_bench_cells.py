"""BENCHMARK.json resolves by name, the generators match the program's,
and the plain reference agrees with brute force and with the service."""
import itertools
import json
import os
import re

import numpy as np
import pytest

from bench import harness, reference
from bench.graphs import make_graph, zipf_edges
from bench.traffic import Traffic, family, load as load_traffic

ROOT = harness.ROOT
ISO = family("iso")
WCLIQUE = family("weighted-clique")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


def test_benchmark_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_cell_resolves(bench):
    used = set()
    for w in bench["workloads"]:
        cell = harness.resolve(bench, w["name"])
        used.add(w["config"])
        assert cell["chips"] in (1, 4) and cell["config"]["chips"] == \
            cell["chips"]
        fam = family(cell["traffic"]["family"])
        assert callable(fam.Queries) and callable(fam.reference)
        for m in cell["per_layer"]:
            assert callable(harness.metric_reader(m["name"]))
        for key in ("n", "m", "skew"):
            assert key in cell["config"]
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert c["file"].startswith("bench/") and os.path.exists(path)
        with open(path) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert cfg["source"] == c["source"]
        assert cfg["guarantees"]["answer"].startswith("exact top-k")


def test_every_traffic_file_loads():
    for name in os.listdir(os.path.join(harness.BENCH_DIR, "traffic")):
        spec = load_traffic(os.path.join(harness.BENCH_DIR, "traffic", name))
        assert spec["k"] >= 1


def test_a_family_is_found_by_its_file_name(tmp_path):
    mix = dict(loop="closed", clients=4, family="no-such-family", k=10)
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    with pytest.raises(ValueError, match="no-such-family"):
        load_traffic(str(path))


@pytest.mark.parametrize("n,m,skew,seed", [(200, 2000, 0.5, 3),
                                           (64, 2000, 1.0, 1),
                                           (1000, 10800, 0.5, 2 ** 31 + 5)])
def test_edges_match_program_generator(n, m, skew, seed):
    from repro.data.synthetic_graphs import _skewed_edges
    ours = zipf_edges(n, m, skew, np.random.default_rng(seed))
    theirs = _skewed_edges(n, m, skew, np.random.default_rng(seed))
    assert np.array_equal(ours, theirs)


def test_label_counts_do_not_depend_on_the_seed():
    cfg = dict(n=1000, m=10800, skew=0.5, labels=29, label_skew=1.0)
    counts = [np.bincount(make_graph(cfg, s)[2], minlength=29)
              for s in (1, 2, 3 * 2 ** 31)]
    assert counts[0].sum() == 1000 and (counts[0] > 0).all()
    assert all(np.array_equal(counts[0], c) for c in counts)
    assert not np.array_equal(make_graph(cfg, 1)[2], make_graph(cfg, 2)[2])


@pytest.mark.parametrize("mix", ["iso-c4", "wclique-c4"])
def test_traffic_repeats_for_a_seed(mix):
    spec = load_traffic(os.path.join(harness.BENCH_DIR, "traffic",
                                     mix + ".json"))
    labels = np.arange(100) % 5
    a, b = Traffic(spec, 100, labels, 7), Traffic(spec, 100, labels, 7)
    assert [a.query(i) for i in range(20)] == [b.query(i) for i in range(20)]
    assert a.query(0) != Traffic(spec, 100, labels, 8).query(0)
    assert a.warmup() == b.warmup()
    if spec["family"] == "iso":
        # one warm-up query per shape, each query a template of the pool
        assert {len(q["q_edges"]) for q in a.warmup()} == \
            {len(e) for e in ISO.SHAPES.values()}
        assert all(a.query(i) in a._queries.pool for i in range(20))


# ------------------------------------------------------ reference itself
def brute_cliques(g, w, k):
    best = []
    nb = g.sets
    for size in range(1, 6):
        for c in itertools.combinations(range(g.n), size):
            if all(b in nb[a] for a, b in itertools.combinations(c, 2)):
                best.append((sum(w[v] for v in c), c))
    best.sort(key=lambda r: (-r[0], reference.bitset_words(r[1], g.n)))
    return [r[0] for r in best[:k]], [list(r[1]) for r in best[:k]]


def brute_iso(g, q_edges, q_labels, k):
    nq = len(q_labels)
    order = ISO.expansion_order(q_edges, nq)
    qe = {frozenset(e) for e in q_edges}
    nb = g.sets
    rows = []
    for m in itertools.permutations(range(g.n), nq):
        if any(g.labels[v] != l for v, l in zip(m, q_labels)):
            continue
        if all((m[j] in nb[m[i]]) == (frozenset((i, j)) in qe)
               for i, j in itertools.combinations(range(nq), 2)):
            rows.append((int(sum(g.degree[v] for v in m)),
                         tuple(m[v] for v in order), list(m)))
    rows.sort(key=lambda r: (-r[0], r[1]))
    keys = [r[0] for r in rows[:k]] + [reference.NEG] * (k - len(rows[:k]))
    return keys, [r[2] for r in rows[:k]]


@pytest.mark.parametrize("seed", [0, 1])
def test_reference_matches_brute_force(seed):
    cfg = dict(n=24, m=70, skew=0.5, labels=3, label_skew=1.0)
    n, edges, labels = make_graph(cfg, seed)
    g = reference.Graph(n, edges, labels)
    rng = np.random.default_rng(seed)
    w = rng.integers(1, 4, size=n)          # small weights: many ties
    assert WCLIQUE.reference(g, dict(weights=w, k=12)) == \
        brute_cliques(g, w, 12)
    for shape in ISO.SHAPES.values():
        nq = 1 + max(max(e) for e in shape)
        q_labels = [int(x) for x in rng.integers(0, 3, size=nq)]
        assert ISO.reference(g, dict(q_edges=shape, q_labels=q_labels,
                                     k=6)) == brute_iso(g, shape, q_labels, 6)


# ------------------------------------------------- reference vs service
@pytest.mark.parametrize("config", ["mico-wclique", "mico-iso"])
def test_reference_matches_service(config):
    from repro.core.graph import GraphStore
    from repro.service import DiscoveryRequest, DiscoveryService, \
        GraphRegistry
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    family = "wclique-c4" if config == "mico-wclique" else "iso-c4"
    spec = load_traffic(os.path.join(harness.BENCH_DIR, "traffic",
                                     family + ".json"))
    cfg = dict(cfg, n=200, m=2160)
    for seed in (0, 1):
        n, edges, labels = make_graph(cfg, seed)
        reg = GraphRegistry()
        reg.register("g", GraphStore.from_edges(n, edges, labels=labels))
        svc = DiscoveryService(registry=reg)
        g = reference.Graph(n, edges, labels)
        traffic = Traffic(spec, n, labels, seed)
        queries = [traffic.query(i) for i in range(3)] if \
            spec["family"] == "weighted-clique" else traffic.warmup()
        resps = svc.serve([DiscoveryRequest(graph="g", **q)
                           for q in queries])
        for q, r in zip(queries, resps):
            assert r.status == "ok" and r.terminated == "complete"
            keys, results = traffic.reference(g, q)
            assert (r.result_keys, r.results) == (keys, results)
