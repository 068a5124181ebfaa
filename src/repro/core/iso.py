"""Top-k subgraph isomorphism on the engine (paper §4.3, Ullmann [54] +
Gupta-style index [23]).

Finds the k highest-scored subgraphs of a labeled data graph isomorphic to a
query graph, score = Σ degree of matched data vertices.  Semantics follow the
paper's definition (§2.1): the bijection preserves labels and adjacency *iff*
(induced isomorphism).

State layout (``S = nq + 2`` int32): ``mapping[nq]`` (data vertex per query
vertex, -1 unmatched), ``depth`` (matched count), ``score``.

Targeted expansion: the candidate set for the next query vertex ``j`` is
computed as a bitset intersection over all already-matched query vertices
``i`` — ``adj(map[i])`` when ``(i,j) ∈ E_q`` and its complement otherwise —
AND the label-``l_j`` vertex bitset (or the OR-ed bitset of ``j``'s label
class under a :class:`~repro.core.labels.LabelPredicate`), minus used
vertices.  Only vertices in that set are ever materialized (Ullmann-style
forward checking).  Label predicates push down into the same product:
the allowed-vertex bitset seeds the constraint mask and ``edge_any_of``
swaps in the type-restricted adjacency (DESIGN.md §12).

Pruning/prioritization: the per-vertex index ``index[v, l, h]`` = max degree
over label-``l`` vertices exactly ``h`` hops from ``v`` (paper Fig. 7) gives
``u(s) = Σ_{unmatched t} index[seed, label_q(t), hop_q(t)]``; priority is the
paper's ``(edgeCount, score + u)`` and ``dominated`` compares ``score + u``
with the k-th result score.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import bitset
from .api import NEG, SubgraphComputation
from .graph import GraphStore, gather_csr
from .labels import LABEL_FILTERS, LabelPredicate


# ----------------------------------------------------------------- the index
def build_iso_index(graph: GraphStore, max_hops: int,
                    predicate: Optional[LabelPredicate] = None
                    ) -> np.ndarray:
    """``index[v, l, h]`` = max degree over label-l vertices exactly h hops
    from v (h in 1..max_hops; h index 0 is hop 1).  Shape [N, L, H].

    Built host-side by sparse frontier expansion: each hop extends every
    (source, vertex) pair of the last level over the CSR adjacency, so the
    work is the number of pairs within ``max_hops`` (about ``N·d^h`` on a
    degree-``d`` graph), never the ``N×N`` of a dense reachability matrix
    — which would not fit a chip's memory at the widths the bitset layout
    serves.

    When a predicate restricts edge types (``edge_any_of``), hop
    reachability must be computed on the *restricted* adjacency — full-
    graph hop distances do not bound restricted-graph ones, so the full
    index would be unsound for label-constrained queries (a valid match
    at restricted distance h can sit at full distance < h and miss its
    exact-hop index slot).  Degrees stay full-graph: the relevance score
    is the full-graph degree sum regardless of the predicate
    (DESIGN.md §12).  Pass the same predicate here and to
    :func:`make_iso_computation`; the service layer keys its index cache
    by (graph fingerprint, max_hops, allowed edge types) and does this
    automatically.
    """
    assert graph.labels is not None, "iso index requires a labeled graph"
    n = graph.n
    n_labels = int(graph.labels.max()) + 1
    indptr, nbr = graph.indptr, graph.indices
    if predicate is not None and predicate.edge_any_of is not None:
        # the CSR restricted to the allowed slots, rows kept in place
        keep = predicate.edge_mask_csr(graph)
        indptr = np.concatenate([[0], np.cumsum(keep)])[indptr]
        nbr = nbr[keep]
    deg = graph.degrees.astype(np.int32)
    labels = np.asarray(graph.labels, np.int64)

    index = np.zeros((n, n_labels, max_hops), np.int32)
    # (source, vertex) pairs as sorted keys source * n + vertex
    reached = np.arange(n, dtype=np.int64) * (n + 1)   # within h-1 hops
    frontier = reached
    for h in range(max_hops):
        src, u = np.divmod(frontier, n)
        rows, v, _ = gather_csr(indptr, nbr, u)
        reach = np.unique(src[rows] * n + v)
        level = np.setdiff1d(reach, reached, assume_unique=True)
        reached = np.union1d(reached, level)
        frontier = level                               # exactly h+1 hops
        src, u = np.divmod(level, n)
        best = np.zeros(n * n_labels, np.int32)
        np.maximum.at(best, src * n_labels + labels[u], deg[u])
        index[:, :, h] = best.reshape(n, n_labels)
    return index


def _query_order(q_edges: Sequence[Tuple[int, int]], nq: int) -> List[int]:
    """BFS order from query vertex 0 so every matched vertex has a matched
    neighbor (connected expansion)."""
    adj = [[] for _ in range(nq)]
    for a, b in q_edges:
        adj[a].append(b)
        adj[b].append(a)
    order, seen = [0], {0}
    i = 0
    while len(order) < nq:
        if i >= len(order):                      # disconnected query
            rest = [v for v in range(nq) if v not in seen]
            order.append(rest[0])
            seen.add(rest[0])
            continue
        for u in sorted(adj[order[i]]):
            if u not in seen:
                order.append(u)
                seen.add(u)
        i += 1
    return order


def _query_hops(q_edges, nq) -> np.ndarray:
    """Hop distance from query vertex 0 inside the query graph."""
    adj = [[] for _ in range(nq)]
    for a, b in q_edges:
        adj[a].append(b)
        adj[b].append(a)
    dist = np.full(nq, nq, np.int32)
    dist[0] = 0
    frontier = [0]
    d = 0
    while frontier:
        d += 1
        nxt = []
        for v in frontier:
            for u in adj[v]:
                if dist[u] > d:
                    dist[u] = d
                    nxt.append(u)
        frontier = nxt
    return dist


def make_iso_computation(graph: GraphStore,
                         q_edges: Sequence[Tuple[int, int]],
                         q_labels: Sequence[int],
                         index: np.ndarray,
                         induced: bool = True,
                         use_pallas: bool = False,
                         interpret: Optional[bool] = None,
                         cand_path: str = "batched",
                         predicate: Optional[LabelPredicate] = None,
                         label_filter: str = "pushdown"
                         ) -> SubgraphComputation:
    """Build the iso :class:`SubgraphComputation`.

    Candidate-generation path (byte-identical results, DESIGN.md §10):

    * ``use_pallas=True`` — batched constraint product, then the
      masked-intersection Pallas kernel materializes the [B, N] candidate
      grid for the whole dequeued batch in one call (``interpret=None``
      auto-detects the backend; ``cand_path`` is ignored);
    * ``cand_path="batched"`` (default) — same batched constraint
      product, jnp membership unpack (the kernel's reference path);
    * ``cand_path="vmap"`` — the legacy per-state ``fori_loop`` under
      ``vmap``;
    * ``cand_path="map"`` — the per-state loop run truly one state at a
      time (``lax.map``), the paper's Algorithm-1 form and the baseline
      ``benchmarks/bench_iso.py`` measures the batched paths against.

    Label-constrained discovery (DESIGN.md §12): ``predicate`` restricts
    which data vertices/edges may participate.  ``q_any_of`` replaces the
    exact per-query-vertex label with a label *class* (the row operand of
    the kernel becomes the class's OR-ed label bitset); ``edge_any_of``
    swaps the constraint product's adjacency for the type-restricted
    adjacency (both structural — they change matching semantics and apply
    in every mode).  ``vertex_any_of`` is a pure filter with two
    placements selected by ``label_filter``:

    * ``"pushdown"`` — the allowed-vertex bitset seeds the per-row
      constraint mask of the masked-intersection kernel (infeasible
      candidates die inside the kernel at no extra pass) *and* the
      priority index is restricted to allowed labels, so states with no
      label-feasible extension are dominance-pruned before expansion —
      the paper's proactive pruning;
    * ``"post"`` — the unconstrained candidate grid is materialized and
      the predicate is applied afterwards as a boolean AND (the
      host-side-filtering baseline; the upper-bound index never sees the
      predicate).

    Complete runs return byte-identical top-k in both modes
    (``benchmarks/bench_labeled.py`` asserts it while measuring the
    pushdown win); budget-truncated runs may differ, which is why
    ``label_filter`` joins the service result-cache key.
    """
    assert cand_path in ("batched", "vmap", "map"), cand_path
    assert label_filter in LABEL_FILTERS, label_filter
    assert graph.labels is not None
    if predicate is not None:
        predicate.validate(graph, "iso", nq=len(q_labels))
    n = graph.n
    nq = len(q_labels)
    S = nq + 2
    w = bitset.num_words(n)

    # reorder query vertices so expansion is always connected
    order = _query_order(q_edges, nq)
    inv = {v: i for i, v in enumerate(order)}
    q_labels_o = np.asarray([q_labels[v] for v in order], np.int32)
    q_adj_o = np.zeros((nq, nq), bool)
    for a, b in q_edges:
        q_adj_o[inv[a], inv[b]] = q_adj_o[inv[b], inv[a]] = True
    hops_o = _query_hops(q_edges, nq)[order]       # distance from seed vertex

    # per-query-vertex label classes (exact q_labels when no q_any_of),
    # in expansion order
    if predicate is not None and predicate.q_any_of is not None:
        classes_o = [tuple(predicate.q_any_of[v]) for v in order]
    else:
        classes_o = [(int(l),) for l in q_labels_o]
    # the global vertex predicate, as packed bitset + boolean vector
    allowed_vbits = predicate.vertex_bits(graph) if predicate else None
    allowed_vmask = predicate.vertex_mask(graph) if predicate else None
    pushdown = label_filter == "pushdown"

    max_hops = index.shape[2]
    hops_clamped = np.clip(hops_o, 1, max_hops)
    # ub_rest[v, d] = Σ_{t >= d} max_{l ∈ L_t} index[v, l, hop(t)] (seed = v)
    # where L_t is slot t's label class — under pushdown additionally
    # intersected with the allowed-label set, which tightens the bound
    # (still sound: it over-approximates the best completion that satisfies
    # the predicate).  The post baseline keeps the unrestricted classes.
    per_t = np.zeros((n, nq), np.int32)
    for t in range(nq):
        lt = classes_o[t]
        if pushdown and predicate is not None and \
                predicate.vertex_any_of is not None:
            lt = tuple(l for l in lt if l in predicate.vertex_any_of)
        if lt:
            per_t[:, t] = index[:, list(lt), hops_clamped[t] - 1].max(axis=1)
    suffix = np.cumsum(per_t[:, ::-1], axis=1)[:, ::-1]     # [N, nq]
    ub_rest = np.concatenate(
        [suffix, np.zeros((n, 1), np.int32)], axis=1)       # [N, nq+1]

    # constraint-product adjacency: restricted to allowed edge types when
    # the predicate carries edge_any_of (structural; both filter modes)
    adjc = predicate.adjacency(graph) if predicate is not None \
        else graph.adj_bits
    # class bitsets: the kernel's per-row label operand, one row per slot
    class_bits = np.stack([
        np.bitwise_or.reduce(graph.label_bits[list(cls)], axis=0)
        for cls in classes_o])                              # [nq, W]

    # graph-sized device tables: passed into the engine's jitted programs
    # as arguments (SubgraphComputation.tables), never closed over
    tables = dict(deg=jnp.asarray(graph.degrees, jnp.int32),
                  adj=jnp.asarray(adjc),
                  class_bits=jnp.asarray(class_bits),
                  ub_rest=jnp.asarray(ub_rest, jnp.int32),
                  eye=jnp.asarray(bitset.eye_table(n)))
    if allowed_vbits is not None:
        tables["allowed_vbits"] = jnp.asarray(allowed_vbits)
        tables["allowed_vmask"] = jnp.asarray(allowed_vmask)
    q_adj_d = jnp.asarray(q_adj_o)
    if use_pallas:
        from repro.kernels import ops as kops

    max_deg = int(graph.degrees.max())
    base = int(2 * nq * max_deg + max_deg + 2)     # lexicographic stride
    assert (nq + 1) * base < 2 ** 31

    full_word = jnp.uint32(0xFFFFFFFF)

    def _cand_parts(states, t):
        """Batched candidate generation for a whole dequeued batch: per-row
        label bitsets and constraint masks (adjacency/complement products
        ∧ ~used), one gather + AND-reduce instead of a per-state loop.

        The candidate set of state ``b`` is ``lbl[b] & mask[b]``; the two
        parts are returned separately because they are exactly the
        (rows, row-mask) operands of the masked-intersection kernel.

        The constraint-slot loop is statically unrolled over ``nq`` with
        [B, W]-shaped operations only — no sequential ``fori_loop`` carry
        and no [B, nq, W] temporaries, which is what makes this path
        faster than the per-state loop (benchmarks/bench_iso.py).
        """
        b = states.shape[0]
        mapping = states[:, :nq]                        # [B, nq]
        d = states[:, nq]                               # [B]
        j = jnp.minimum(d, nq - 1)
        lbl = t["class_bits"][j]                        # [B, W]
        if pushdown and "allowed_vbits" in t:
            # predicate pushdown: the allowed-vertex bitset seeds the
            # per-row kernel mask, so label-infeasible candidates are
            # culled inside the masked intersection (DESIGN.md §12)
            mask = jnp.broadcast_to(t["allowed_vbits"], (b, w))
        else:
            mask = jnp.full((b, w), full_word)
        used = jnp.zeros((b, w), jnp.uint32)
        for i in range(nq):                             # static: nq small
            mi = jnp.maximum(mapping[:, i], 0)          # [B]
            row = t["adj"][mi]                          # [B, W]
            need = q_adj_d[i][j]                        # [B] (q_adj symmetric)
            con = jnp.where(need[:, None], row, ~row) if induced else \
                jnp.where(need[:, None], row, full_word)
            active = (i < d)[:, None]                   # [B, 1]
            mask = jnp.where(active, mask & con, mask)
            used = jnp.where(active, used | t["eye"][mi], used)
        mask = mask & ~used
        return lbl, jnp.where((d < nq)[:, None], mask, jnp.uint32(0))

    def _cand_bits(state, t):
        """Per-state loop form of :func:`_cand_parts` (legacy reference,
        kept for the `cand_path="vmap"/"map"` benchmark baselines)."""
        mapping = state[:nq]
        d = state[nq]
        j = jnp.minimum(d, nq - 1)
        acc = t["class_bits"][j]
        if pushdown and "allowed_vbits" in t:
            acc = acc & t["allowed_vbits"]

        def body(i, carry):
            acc, used = carry
            mi = jnp.maximum(mapping[i], 0)
            row = t["adj"][mi]
            need = q_adj_d[i, j]
            constraint = jnp.where(need, row, ~row) if induced else \
                jnp.where(need, row, jnp.uint32(0xFFFFFFFF))
            active = i < d
            acc = jnp.where(active, acc & constraint, acc)
            used = jnp.where(active, bitset.set_bit(used, mi), used)
            return acc, used

        acc, used = jax.lax.fori_loop(
            0, nq, body, (acc, jnp.zeros((w,), jnp.uint32)))
        acc = acc & ~used
        return jnp.where(d < nq, acc, jnp.zeros((w,), jnp.uint32))

    # seed = vertices matching slot 0's label class; the vertex predicate
    # applies here in BOTH filter modes — an unfiltered disallowed seed
    # could complete into a violating result (the post mode only defers
    # filtering of *candidate* vertices)
    seed_ok = np.isin(np.asarray(graph.labels), list(classes_o[0]))
    if allowed_vmask is not None:
        seed_ok &= allowed_vmask
    seeds = np.nonzero(seed_ok)[0].astype(np.int32)

    def init_frontier(t):
        n0 = len(seeds)
        sc = t["deg"][seeds]
        states = jnp.full((n0, S), -1, jnp.int32)
        states = states.at[:, 0].set(seeds)
        states = states.at[:, nq].set(1)                     # depth
        states = states.at[:, nq + 1].set(sc)
        ub = sc + t["ub_rest"][seeds, 1]
        return states, 1 * base + ub, ub

    def score_children(states, t):
        if use_pallas:
            lbl, mask = _cand_parts(states, t)
            in_cand = kops.masked_intersect(
                lbl, t["eye"], mask, interpret=interpret) > 0    # [B, N]
        elif cand_path == "batched":
            lbl, mask = _cand_parts(states, t)
            in_cand = bitset.to_bool(lbl & mask, n)              # [B, N]
        elif cand_path == "vmap":
            cand = jax.vmap(lambda s: _cand_bits(s, t))(states)  # [B, W]
            in_cand = bitset.to_bool(cand, n)                    # [B, N]
        else:  # "map": one state at a time (the pre-batching loop form)
            cand = jax.lax.map(lambda s: _cand_bits(s, t), states)
            in_cand = bitset.to_bool(cand, n)                    # [B, N]
        if not pushdown and "allowed_vmask" in t:
            # host-side-filter baseline: the unconstrained candidate grid
            # was materialized above; the predicate lands only now
            in_cand = in_cand & t["allowed_vmask"][None, :]
        d = states[:, nq]
        score = states[:, nq + 1]
        seed = jnp.maximum(states[:, 0], 0)
        nd = jnp.minimum(d + 1, nq)
        rest = t["ub_rest"][seed, nd]                        # [B]
        child_score = score[:, None] + t["deg"][None, :]
        child_ub = child_score + rest[:, None]
        child_prio = nd[:, None] * base + child_ub
        invalid = ~in_cand
        return (jnp.where(invalid, NEG, child_prio),
                jnp.where(invalid, NEG, child_ub))

    def materialize(states, actions, t):
        d = states[:, nq]
        b = states.shape[0]
        row = jnp.arange(b)
        out = states.at[row, d].set(actions)
        out = out.at[row, nq].add(1)
        out = out.at[row, nq + 1].add(t["deg"][actions])
        return out

    def result_key(states, t):
        complete = states[:, nq] == nq
        return jnp.where(complete, states[:, nq + 1], NEG)

    def upper_bound(states, t):
        d = states[:, nq]
        seed = jnp.maximum(states[:, 0], 0)
        return states[:, nq + 1] + t["ub_rest"][seed, jnp.minimum(d, nq)]

    def describe(state_row: np.ndarray) -> list:
        m = list(map(int, state_row[:nq]))
        return [m[inv[v]] for v in range(nq)]    # original query order

    return SubgraphComputation(
        name="iso", state_width=S, num_actions=n,
        init_frontier=init_frontier, score_children=score_children,
        materialize=materialize, result_key=result_key,
        upper_bound=upper_bound, describe=describe, tables=tables)
