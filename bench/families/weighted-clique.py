"""Weighted clique: top-k cliques ranked by the sum of per-vertex weights
that each query supplies (the paper's Listing 1, the service's
``weighted-clique`` workload).

Mix keys: ``weights``, ``{"dist": "log-uniform", "low", "high"}``,
integer vertex weights drawn afresh for every query, so every query is a
new weighting of the same program.

The plain answer: every clique of the graph is a result, keyed by the sum
of its vertices' weights.  Ties are broken by the clique's vertex bitset
(32 vertices to a word, vertex ``v`` at bit ``v % 32`` of word ``v //
32``), read as signed 32-bit words, lexicographically ascending.  A result
is the clique's sorted vertex list.
"""
import numpy as np

from bench.reference import TopK, bitset_words
from bench.traffic import WARMUP, WINDOW, stream

WORKLOAD = "weighted-clique"


class Queries:
    def __init__(self, spec: dict, n: int, labels, seed: int):
        dist = spec["weights"]
        if dist["dist"] != "log-uniform":
            raise ValueError(f"unknown weight distribution {dist['dist']!r}")
        self.lo, self.hi = int(dist["low"]), int(dist["high"])
        self.k = int(spec["k"])
        self.n = n
        self.seed = seed

    def _query(self, rng: np.random.Generator) -> dict:
        w = np.exp(rng.uniform(np.log(self.lo), np.log(self.hi + 1),
                               size=self.n))
        weights = np.clip(w.astype(np.int64), self.lo, self.hi)
        return dict(workload=WORKLOAD, k=self.k,
                    weights=tuple(int(x) for x in weights))

    def query(self, i: int) -> dict:
        return self._query(stream(self.seed, WINDOW, i))

    def warmup(self) -> list:
        """One query: every weighting runs the same programs."""
        return [self._query(stream(self.seed, WARMUP, 0))]


def reference(g, query: dict):
    """Top-k cliques by weight, by depth-first branch and bound that
    prunes a branch only when its bound falls strictly below the current
    k-th key: ``(keys, results)``."""
    w = [int(x) for x in query["weights"]]
    nb = g.sets
    later = [g.neighbours(v)[g.neighbours(v) > v].tolist()
             for v in range(g.n)]                 # ascending ids
    top = TopK(int(query["k"]))

    def grow(clique: list, wc: int, cand: list) -> None:
        top.offer(wc, tuple(clique))
        # suffix[i] = weight of cand[i:], the most any child from cand[i]
        # on can add
        suffix = np.cumsum([w[u] for u in cand][::-1])[::-1].tolist()
        for i, a in enumerate(cand):
            if wc + suffix[i] < top.threshold:
                break
            na = nb[a]
            sub = [u for u in cand[i + 1:] if u in na]
            wa = wc + w[a]
            if wa + sum(w[u] for u in sub) >= top.threshold:
                grow(clique + [a], wa, sub)

    bound = [w[v] + sum(w[u] for u in later[v]) for v in range(g.n)]
    for v in sorted(range(g.n), key=lambda v: (-bound[v], v)):
        if bound[v] < top.threshold:
            break
        grow([v], w[v], later[v])
    keys, rows = top.best(lambda c: bitset_words(c, g.n))
    return keys, [list(c) for c in rows]
