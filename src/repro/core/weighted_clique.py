"""Maximum-WEIGHT clique discovery — written against the paper's succinct
per-subgraph API (:func:`repro.core.api.from_pointwise`), the Python analog
of the paper's Listing 1.

Demonstrates the Table-1 generality claim: a new top-k computation is a
few scalar functions (relevant+result / dominated / materialize); the
engine, batching, pruning, and VPQ come for free.  Child scoring, the hot
loop, is the one batched function (:func:`weighted_counts`): the weighted
popcount ``w(P_b ∩ ext[a])`` of the whole ``[B, N]`` grid is one exact
integer matrix product on the MXU.

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

# Weights enter the matrix product as unsigned 8-bit digits, each exact in
# bfloat16; four of them cover every weight below 2**30 (weight_table).
_DIGIT_BITS = 8
_NUM_DIGITS = 4
_DIGIT_MAX = 2 ** _DIGIT_BITS - 1
# float32 holds every integer below 2**24 exactly
_F32_EXACT = 2 ** 24


def contraction_chunks(num_words: int) -> list:
    """Word ranges ``[lo, hi)`` that split a weighted popcount over
    ``num_words`` bitset words into matrix products whose float32 partial
    sums stay exact: a chunk of ``L`` bits sums at most ``L`` digits of
    ``_DIGIT_MAX``, which must stay below ``2**24``.  One range up to
    65,792 vertices."""
    step = (_F32_EXACT - 1) // (bitset.WORD_BITS * _DIGIT_MAX)
    return [(lo, min(lo + step, num_words))
            for lo in range(0, num_words, step)]


def weighted_counts(p_bits: jnp.ndarray, ext: jnp.ndarray,
                    wt: jnp.ndarray) -> jnp.ndarray:
    """``out[b, a] = w(P_b ∩ ext[a])`` = Σ_u w[u]·[u ∈ P_b]·[u ∈ ext[a]],
    exactly, for ``p_bits`` ``[B, W]``, ``ext`` ``[N, W]`` (uint32 bitsets)
    and ``wt`` ``int32[N]`` summing below 2**30.

    The 0/1 ``P`` rows, scaled by each 8-bit digit of ``w``, are stacked
    into ``[D·B, 32W]`` bfloat16 rows and contracted with the unpacked 0/1
    ``ext`` in one ``dot_general`` per chunk of :func:`contraction_chunks`,
    accumulating in float32; every operand and partial sum is an integer
    its dtype holds exactly, so the digits recombine in int32 to the exact
    sum."""
    b, w = p_bits.shape
    n = ext.shape[0]
    width = w * bitset.WORD_BITS
    in_p = bitset.to_bool(p_bits, width)                     # [B, 32W]
    wt = jnp.pad(wt, (0, width - wt.shape[0]))
    shifts = _DIGIT_BITS * jnp.arange(_NUM_DIGITS, dtype=jnp.int32)
    digits = (wt[None, :] >> shifts[:, None]) & _DIGIT_MAX    # [D, 32W]
    rows = jnp.where(in_p[None], digits[:, None, :], 0)
    rows = rows.reshape(_NUM_DIGITS * b, width).astype(jnp.bfloat16)
    acc = jnp.zeros((_NUM_DIGITS * b, n), jnp.int32)
    for lo, hi in contraction_chunks(w):
        cols = bitset.to_bool(ext[:, lo:hi], (hi - lo) * bitset.WORD_BITS)
        part = jax.lax.dot_general(
            rows[:, lo * bitset.WORD_BITS:hi * bitset.WORD_BITS],
            cols.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc + part.astype(jnp.int32)
    acc = acc.reshape(_NUM_DIGITS, b, n)
    return sum(acc[k] << (_DIGIT_BITS * k) for k in range(_NUM_DIGITS))


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

    # ----- the paper's user functions; scoring batched over [B, N] -----
    def _unpack(s):
        return (bitset.to_u32(s[:w]), bitset.to_u32(s[w:2 * w]),
                s[2 * w], s[2 * w + 1])

    def score_children(states, t):
        # prio = ub = w(V) + w[a] + w(P ∩ ext[a]); NEG where a ∉ P
        p_bits = bitset.to_u32(states[:, w:2 * w])
        score = (states[:, 2 * w, None] + t["w"][None, :]
                 + weighted_counts(p_bits, t["ext"], t["w"]))
        score = jnp.where(bitset.to_bool(p_bits, n), score, NEG)
        return score, score

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
        init_frontier=init_frontier, score_children=score_children,
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
