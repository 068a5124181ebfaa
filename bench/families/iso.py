"""Induced subgraph isomorphism: top-k matches of a small labelled query
graph, scored by the sum of the matched vertices' degrees (the service's
``iso`` workload).

Mix keys: ``shapes``, the query shapes to draw from (names in
``SHAPES``), and ``pool``, the number of query templates (a shape, and a
label per query vertex drawn as the label of a uniformly drawn graph
vertex) made from the seed; each query picks a template uniformly.

The plain answer: every injective, label-preserving map of the query
vertices onto data vertices under which query edges and data edges
coincide is a result, keyed by the sum of the matched vertices' degrees.
Ties are broken by the matched vertices listed in the query's expansion
order (breadth-first from query vertex 0, neighbours in ascending order),
lexicographically ascending.  A result lists the data vertex of each
query vertex, in query order.
"""
from typing import List, Sequence, Tuple

import numpy as np

from bench.reference import TopK
from bench.traffic import POOL, WARMUP, WINDOW, stream

WORKLOAD = "iso"

# the connected query graphs on 3 and 4 vertices
SHAPES = {
    "path3": ((0, 1), (1, 2)),
    "triangle": ((0, 1), (1, 2), (0, 2)),
    "path4": ((0, 1), (1, 2), (2, 3)),
    "star4": ((0, 1), (0, 2), (0, 3)),
    "cycle4": ((0, 1), (1, 2), (2, 3), (0, 3)),
    "paw": ((0, 1), (1, 2), (0, 2), (2, 3)),
    "diamond": ((0, 1), (1, 2), (2, 3), (0, 3), (0, 2)),
    "clique4": ((0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)),
}


class Queries:
    def __init__(self, spec: dict, n: int, labels, seed: int):
        self.shapes = list(spec["shapes"])
        unknown = set(self.shapes) - set(SHAPES)
        if unknown:
            raise ValueError(f"unknown query shapes {sorted(unknown)}")
        self.k = int(spec["k"])
        self.n = n
        self.labels = labels
        self.seed = seed
        rng = stream(seed, POOL)
        self.pool = [self._template(rng, self.shapes[int(
            rng.integers(len(self.shapes)))]) for _ in range(spec["pool"])]

    def _template(self, rng: np.random.Generator, shape: str) -> dict:
        edges = SHAPES[shape]
        nq = 1 + max(max(e) for e in edges)
        q_labels = self.labels[rng.integers(0, self.n, size=nq)]
        return dict(workload=WORKLOAD, k=self.k, q_edges=edges,
                    q_labels=tuple(int(x) for x in q_labels))

    def query(self, i: int) -> dict:
        rng = stream(self.seed, WINDOW, i)
        return dict(self.pool[int(rng.integers(len(self.pool)))])

    def warmup(self) -> list:
        """One query per shape: the shape is a constant of the program."""
        rng = stream(self.seed, WARMUP)
        return [self._template(rng, s) for s in self.shapes]


def expansion_order(q_edges: Sequence[Tuple[int, int]], nq: int) -> List[int]:
    """Query vertices breadth-first from vertex 0, neighbours ascending
    (a disconnected rest follows in ascending order)."""
    adj = [set() for _ in range(nq)]
    for a, b in q_edges:
        adj[a].add(b)
        adj[b].add(a)
    order = [0]
    i = 0
    while len(order) < nq:
        if i == len(order):
            order.append(min(set(range(nq)) - set(order)))
        for u in sorted(adj[order[i]]):
            if u not in order:
                order.append(u)
        i += 1
    return order


def reference(g, query: dict):
    """Top-k induced matches of the labelled query by degree sum, by
    depth-first branch and bound that prunes a branch only when its bound
    falls strictly below the current k-th key: ``(keys, results)``."""
    q_edges, q_labels = query["q_edges"], query["q_labels"]
    nq = len(q_labels)
    order = expansion_order(q_edges, nq)
    pos = {v: i for i, v in enumerate(order)}
    edge = np.zeros((nq, nq), bool)               # in expansion order
    for a, b in q_edges:
        edge[pos[a], pos[b]] = edge[pos[b], pos[a]] = True
    lab = [int(q_labels[v]) for v in order]
    deg = g.degree.tolist()
    labels = g.labels
    n_labels = int(labels.max()) + 1
    maxdeg = np.zeros(n_labels + 1, np.int64)
    np.maximum.at(maxdeg, labels, g.degree)
    # rest[j]: the most the query vertices from position j on can add
    rest = np.concatenate([np.cumsum([maxdeg[l] if l < n_labels else 0
                                      for l in lab][::-1])[::-1], [0]])
    rest = rest.tolist()
    nb = g.sets
    top = TopK(int(query["k"]))
    by_label = {}
    for l in set(lab):
        vs = np.nonzero(labels == l)[0]
        by_label[l] = vs[np.argsort(-g.degree[vs], kind="stable")].tolist()
    parent = [next((i for i in range(j) if edge[i, j]), None)
              for j in range(nq)]

    def extend(mapping: list, score: int) -> None:
        j = len(mapping)
        if j == nq:
            top.offer(score, tuple(mapping))
            return
        if parent[j] is None:
            cands = by_label.get(lab[j], [])
        else:
            p = parent[j]
            cands = [v for v in g.neighbours(mapping[p]).tolist()
                     if labels[v] == lab[j]]
            cands.sort(key=lambda v: -deg[v])
        for v in cands:
            if score + deg[v] + rest[j + 1] < top.threshold:
                break                              # degrees only fall
            if v in mapping:
                continue
            nv = nb[v]
            if all((mapping[i] in nv) == edge[i, j] for i in range(j)):
                extend(mapping + [v], score + deg[v])

    extend([], 0)
    keys, rows = top.best(lambda m: m)
    return keys, [[m[pos[v]] for v in range(nq)] for m in rows]
