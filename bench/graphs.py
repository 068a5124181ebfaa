"""Data graphs of a deployment, generated from a seed.

A configuration names its graph by a few numbers (``n`` vertices, ``m``
undirected edges, the Zipf ``skew`` of the edge endpoints and, for a
labelled graph, ``labels`` vertex labels with a Zipf ``label_skew``).
:func:`make_graph` turns them and a seed into plain numpy arrays, which the
harness hands to the program and the reference alike.

:func:`zipf_edges` is a vectorised copy of the program's
``repro.data.synthetic_graphs._skewed_edges``: for the same generator state
it draws the same candidates and keeps the same ones (the first
occurrence of each new pair, in draw order), so it gives the same graph.
The benchmark keeps its own copy so that a change to the program cannot
move the yardstick.
"""
from __future__ import annotations

import numpy as np


def zipf_weights(n: int, skew: float) -> np.ndarray:
    """Probability of each of ``n`` items, proportional to (i+1)**-skew."""
    p = (np.arange(n) + 1.0) ** -float(skew)
    return p / p.sum()


def zipf_edges(n: int, m: int, skew: float,
               rng: np.random.Generator) -> np.ndarray:
    """``m`` distinct undirected edges ``[m, 2]`` (u < v) whose endpoints
    are drawn with probability proportional to ``(v + 1) ** -skew``."""
    if not 0 < m <= n * (n - 1) // 2:
        raise ValueError(f"cannot draw {m} distinct edges on {n} vertices")
    p = zipf_weights(n, skew)
    seen = np.empty(0, np.int64)            # keys u * n + v, sorted
    edges = np.empty((0, 2), np.int64)
    while len(edges) < m:
        need = m - len(edges)
        cand = rng.choice(n, size=(2 * need + 64, 2), p=p)
        cand = cand[cand[:, 0] != cand[:, 1]]
        cand.sort(axis=1)
        key = cand[:, 0] * n + cand[:, 1]
        _, first = np.unique(key, return_index=True)
        fresh = np.zeros(len(key), bool)
        fresh[first] = True
        fresh &= ~np.isin(key, seen)
        take = np.nonzero(fresh)[0][:need]
        edges = np.concatenate([edges, cand[take]])
        # the original marks every pair of the draw as seen, also those
        # past the first ``need`` fresh ones that it does not keep
        seen = np.union1d(seen, key)
    return edges


def make_graph(cfg: dict, seed: int):
    """The configuration's graph for ``seed``: ``(n, edges, labels)``,
    with ``labels`` None for an unlabelled graph.  Label ``l`` of ``L``
    is held by the same number of vertices for every seed, in proportion
    to ``(l + 1) ** -label_skew`` (largest remainders take the rounding),
    and the seed decides which vertices hold it."""
    n, m = int(cfg["n"]), int(cfg["m"])
    edges = zipf_edges(n, m, float(cfg["skew"]),
                       np.random.default_rng([seed, 0]))
    labels = None
    if cfg.get("labels"):
        share = n * zipf_weights(int(cfg["labels"]), float(cfg["label_skew"]))
        count = np.floor(share).astype(np.int64)
        rest = np.argsort(-(share - count), kind="stable")
        count[rest[:n - count.sum()]] += 1
        labels = np.repeat(np.arange(len(count), dtype=np.int32), count)
        labels = np.random.default_rng([seed, 1]).permutation(labels)
    return n, edges, labels
