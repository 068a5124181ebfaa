"""Maximum-clique discovery on the engine (paper §3.2 / §4.1, CP bound [7]).

State layout (``S = 2W + 2`` int32 words, W = bitset words):

* ``[0:W)``      — V bitset (clique members),
* ``[W:2W)``     — P bitset (candidate vertices that keep it a clique,
  restricted to ids greater than the last added vertex — the paper's
  duplicate-avoidance rule, cf. Fig. 2: v1 is not re-added to s2),
* ``[2W]``       — ``|V|`` (clique size),
* ``[2W+1]``     — ``|P|``.

User functions (paper Table 1 → here):

* ``expandable(s, v)``  = ``v ∈ P_s``                      (targeted expansion)
* ``priority(s)``       = lexicographic ``(|V_s|, |P_s|)`` → ``|V|·(N+1)+|P|``
* ``relevant(s)``       = always true (only cliques are ever created)
* ``dominated(s, s')``  = ``|V_s| + |P_s| < |V_{s'}|``     (CP bound)

The child-scoring hot loop — ``popcount(P ∩ N(v) ∩ {u > v})`` for the whole
``[B, N]`` grid — is the compute kernel of the paper's system; it runs either
as pure jnp (reference) or via the Pallas kernel
:mod:`repro.kernels.frontier_expand` (``use_pallas=True``).  The ``[N, W]``
extension masks are the computation's ``tables``: a device argument of the
engine's jitted step, not a constant compiled into it.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import bitset
from .api import NEG, SubgraphComputation
from .graph import GraphStore


def make_clique_computation(graph: GraphStore,
                            use_pallas: bool = False,
                            interpret: Optional[bool] = None
                            ) -> SubgraphComputation:
    """``use_pallas`` selects the Pallas kernel for child scoring;
    ``interpret=None`` auto-detects the backend (DESIGN.md §10).  Both
    paths are byte-identical (tests/test_kernels.py parity suite)."""
    n = graph.n
    w = bitset.num_words(n)
    assert (n + 1) ** 2 < 2 ** 31, "int32 priority keys require N <= ~46k"
    S = 2 * w + 2

    adj = jnp.asarray(graph.adj_bits)                      # [N, W] uint32
    gt = jnp.asarray(bitset.lt_mask_table(n))              # [N, W] uint32
    ext_mask = adj & gt                                    # N(v) ∩ {u > v}
    tables = dict(ext=ext_mask)

    if use_pallas:
        from repro.kernels import ops as kops

    def _unpack(states):
        v_bits = bitset.to_u32(states[..., :w])
        p_bits = bitset.to_u32(states[..., w:2 * w])
        size = states[..., 2 * w]
        pcount = states[..., 2 * w + 1]
        return v_bits, p_bits, size, pcount

    def _pack(v_bits, p_bits, size):
        pcount = bitset.popcount(p_bits)
        return jnp.concatenate([
            bitset.to_i32(v_bits), bitset.to_i32(p_bits),
            size[..., None], pcount[..., None]], axis=-1)

    # ------------------------------------------------------------ callbacks
    def init_frontier(t):
        # unit cliques {v} with P = N(v) ∩ {u > v}  (canonical seeds)
        v_bits = bitset.eye(n)
        p_bits = t["ext"]
        size = jnp.ones((n,), jnp.int32)
        states = _pack(v_bits, p_bits, size)
        pcount = states[:, 2 * w + 1]
        prio = size * (n + 1) + pcount
        ub = size + pcount
        return states, prio, ub

    def score_children(states, t):
        _, p_bits, size, _ = _unpack(states)
        if use_pallas:
            counts = kops.frontier_expand(p_bits, t["ext"],
                                          interpret=interpret)  # [B, N]
        else:
            inter = p_bits[:, None, :] & t["ext"][None, :, :]
            counts = bitset.popcount(inter, axis=-1)         # [B, N]
        in_p = bitset.to_bool(p_bits, n)                     # expandable
        child_prio = jnp.where(in_p, (size[:, None] + 1) * (n + 1) + counts,
                               NEG)
        child_ub = jnp.where(in_p, size[:, None] + 1 + counts, NEG)
        return child_prio, child_ub

    def materialize(states, actions, t):
        v_bits, p_bits, size, _ = _unpack(states)
        new_v = bitset.set_bit(v_bits, actions)
        new_p = p_bits & t["ext"][actions]
        return _pack(new_v, new_p, size + 1)

    def result_key(states, t):
        return states[:, 2 * w]          # clique size; always relevant

    def upper_bound(states, t):
        return states[:, 2 * w] + states[:, 2 * w + 1]

    def describe(state_row: np.ndarray) -> list:
        v_bits = np.asarray(state_row[:w]).view(np.uint32)
        return sorted(int(i) for i in
                      np.nonzero(np.asarray(
                          bitset.to_bool(jnp.asarray(v_bits), n)))[0])

    return SubgraphComputation(
        name="clique", state_width=S, num_actions=n,
        init_frontier=init_frontier, score_children=score_children,
        materialize=materialize, result_key=result_key,
        upper_bound=upper_bound, describe=describe, tables=tables)
