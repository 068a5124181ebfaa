"""Maximum-WEIGHT clique discovery — written against the paper's succinct
per-subgraph API (:func:`repro.core.api.from_pointwise`), the Python analog
of the paper's Listing 1.

Demonstrates the Table-1 generality claim: a new top-k computation is four
scalar functions (expandable / priority / relevant+result / dominated); the
engine, batching, pruning, and VPQ come for free.

State layout (``S = 2W + 2``): V bitset, P bitset, weight(V), weight(P) —
the dominance bound ``w(V) + w(P)`` generalizes the CP cardinality bound.
Weights are positive integers.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import bitset
from .api import NEG, from_pointwise
from .graph import GraphStore


def weight_table(weights) -> np.ndarray:
    """The ``w`` table of a weighting: ``int32[N]``.  Raises ValueError
    unless every weight is positive and their sum fits the int32 priority
    keys."""
    weights = np.asarray(weights, np.int64)
    if (weights <= 0).any():
        raise ValueError("weights must be positive integers")
    if int(weights.sum()) >= 2 ** 30:
        raise ValueError("weights must sum below 2**30 (int32 priority keys)")
    return weights.astype(np.int32)


def make_weighted_clique_computation(graph: GraphStore,
                                     weights: np.ndarray):
    """The weighted-clique computation on ``graph``, with ``weights`` as
    its default ``w`` table.  Only ``w`` depends on the weighting: an
    engine built here serves any other weighting of the graph through
    ``dict(tables, w=weight_table(other))`` (``Engine.start``)."""
    n = graph.n
    w = bitset.num_words(n)
    S = 2 * w + 2

    adj = jnp.asarray(graph.adj_bits)
    gt = jnp.asarray(bitset.lt_mask_table(n))
    tables = dict(ext=adj & gt, w=jnp.asarray(weight_table(weights)))

    def _set_weight(bits, wt):
        # weight of a packed bitset via per-word unpack-dot
        return jnp.sum(jnp.where(bitset.to_bool(bits, n), wt, 0))

    def init_frontier(t):
        v_bits = bitset.eye(n)
        p_bits = t["ext"]
        wv = t["w"]
        wp = jax.vmap(lambda b: _set_weight(b, wv))(p_bits)
        states = jnp.concatenate(
            [bitset.to_i32(v_bits), bitset.to_i32(p_bits),
             wv[:, None], wp[:, None]], axis=-1)
        return states, wv + wp, wv + wp

    # ----- the paper's five user functions, scalar over one state --------
    def _unpack(s):
        return (bitset.to_u32(s[:w]), bitset.to_u32(s[w:2 * w]),
                s[2 * w], s[2 * w + 1])

    def expandable(s, a, t):
        _, p, _, _ = _unpack(s)
        return bitset.get_bit(p[None], jnp.asarray([a]))[0]

    def child_priority(s, a, t):
        _, p, wv, _ = _unpack(s)
        new_p = p & t["ext"][a]
        return wv + t["w"][a] + _set_weight(new_p, t["w"])

    def child_ub(s, a, t):       # same space: weight is the result metric
        return child_priority(s, a, t)

    def materialize_one(s, a, t):
        v, p, wv, _ = _unpack(s)
        new_v = bitset.set_bit(v[None], jnp.asarray([a]))[0]
        new_p = p & t["ext"][a]
        return jnp.concatenate(
            [bitset.to_i32(new_v), bitset.to_i32(new_p),
             (wv + t["w"][a])[None], _set_weight(new_p, t["w"])[None]])

    def relevant(s, t):
        return jnp.bool_(True)   # every expansion is a clique

    def result_key_one(s, t):
        return s[2 * w]          # w(V)

    def upper_bound_one(s, t):
        return s[2 * w] + s[2 * w + 1]   # w(V) + w(P): dominated() bound

    def describe(row):
        v_bits = np.asarray(row[:w]).view(np.uint32)
        return sorted(int(i) for i in np.nonzero(
            np.asarray(bitset.to_bool(jnp.asarray(v_bits), n)))[0])

    return from_pointwise(
        name="weighted-clique", state_width=S, num_actions=n,
        init_frontier=init_frontier, expandable=expandable,
        child_priority=child_priority, child_ub=child_ub,
        materialize_one=materialize_one, relevant=relevant,
        result_key_one=result_key_one, upper_bound_one=upper_bound_one,
        describe=describe, tables=tables)


def brute_force_max_weight_clique(graph: GraphStore, weights: np.ndarray):
    neigh = [set(map(int, graph.neighbors(v))) for v in range(graph.n)]
    best = [0, []]

    def rec(cur, cand, wsum):
        if wsum > best[0]:
            best[0], best[1] = wsum, list(cur)
        if wsum + sum(weights[u] for u in cand) <= best[0]:
            return
        for v in sorted(cand):
            rec(cur + [v], {u for u in cand if u > v and u in neigh[v]},
                wsum + int(weights[v]))

    rec([], set(range(graph.n)), 0)
    return best[0], sorted(best[1])
