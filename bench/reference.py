"""What the plain references of every query family share.

A query family's file under ``families/`` answers its queries with a
straightforward host implementation of the semantics the service
promises, written from the definitions and sharing no code with the
program: exact top-k under the family's canonical tie order.  Each is a
depth-first branch and bound that prunes a branch only when its bound falls
strictly below the current k-th key, so every result tied with the final
k-th key is found before the tie order picks.  Fewer than ``k`` results
pad the keys with ``NEG`` (the minimum 32-bit integer).
"""
from __future__ import annotations

import heapq
from typing import List, Sequence, Tuple

import numpy as np

NEG = -2 ** 31


class Graph:
    """Adjacency of an undirected graph given as an edge array."""

    def __init__(self, n: int, edges: np.ndarray, labels=None):
        self.n = n
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        e = e[e[:, 0] != e[:, 1]]
        e = np.unique(np.sort(e, axis=1), axis=0)
        src = np.concatenate([e[:, 0], e[:, 1]])
        dst = np.concatenate([e[:, 1], e[:, 0]])
        order = np.lexsort((dst, src))
        src, dst = src[order], dst[order]
        self.indptr = np.searchsorted(src, np.arange(n + 1))
        self.nbrs = dst                              # sorted per vertex
        self.degree = np.diff(self.indptr)
        self.labels = None if labels is None else np.asarray(labels)
        self._sets = None

    def neighbours(self, v: int) -> np.ndarray:
        return self.nbrs[self.indptr[v]:self.indptr[v + 1]]

    @property
    def sets(self) -> List[set]:
        if self._sets is None:
            self._sets = [set(self.neighbours(v).tolist())
                          for v in range(self.n)]
        return self._sets


class TopK:
    """The keys of the k best results so far, and every result whose key
    is at least the current k-th key."""

    def __init__(self, k: int):
        self.k = k
        self.heap: List[int] = []
        self.kept: List[Tuple[int, tuple]] = []

    @property
    def threshold(self) -> float:
        return self.heap[0] if len(self.heap) == self.k else -np.inf

    def offer(self, key: int, item: tuple) -> None:
        if key < self.threshold:
            return
        self.kept.append((key, item))
        heapq.heappush(self.heap, key)
        if len(self.heap) > self.k:
            heapq.heappop(self.heap)
        if len(self.kept) > 8 * self.k:
            thr = self.threshold
            self.kept = [r for r in self.kept if r[0] >= thr]

    def best(self, tie_key) -> Tuple[List[int], List[tuple]]:
        thr = self.threshold
        rows = sorted((r for r in self.kept if r[0] >= thr),
                      key=lambda r: (-r[0], tie_key(r[1])))[:self.k]
        keys = [int(r[0]) for r in rows] + [NEG] * (self.k - len(rows))
        return keys, [r[1] for r in rows]


def bitset_words(vertices: Sequence[int], n: int) -> tuple:
    """A vertex set as signed 32-bit words, word 0 first."""
    words = np.zeros((n + 31) // 32, np.uint32)
    for v in vertices:
        words[v // 32] |= np.uint32(1) << np.uint32(v % 32)
    return tuple(words.view(np.int32).tolist())
