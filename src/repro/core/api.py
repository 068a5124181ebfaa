"""User-facing computational model — the paper's Table-1 API, batched.

The paper's five user functions map onto a :class:`SubgraphComputation`:

=====================  =========================================================
paper (Table 1)        here
=====================  =========================================================
``expandable(s, δ)``   fused into ``score_children`` (invalid actions → ``NEG``)
``priority(s)``        the int32 key returned by ``score_children`` /
                       ``init_frontier`` (queue ordering)
``relevant(s)``        ``result_key`` (``NEG`` when not relevant)
``dominated(s, s')``   ``upper_bound`` compared against the k-th result key
``key(s)``             aggregate engine only (:mod:`repro.core.aggregate`)
=====================  =========================================================

Two key spaces exist, exactly as in the paper: the **priority** key orders the
queue (e.g. lexicographic ``(|V_s|, |P_s|)`` for cliques) and the **result**
key ranks the result set (e.g. clique size).  ``upper_bound`` lives in result
space: it must over-approximate the best result key reachable from a state.

API contract (property-tested in ``tests/test_engine_properties.py``):

* ``upper_bound(s) >= result_key(s)`` for every state;
* ``upper_bound(s) >= upper_bound(child)`` for every child of ``s``
  (anti-monotonicity — what makes threshold pruning sound).

States are fixed-width ``int32`` vectors; actions are integers in
``[0, num_actions)``.  The device callbacks take the computation's
``tables`` (a pytree of graph-sized device arrays, e.g. clique's ``[N, W]``
extension masks) as their last argument: the engine passes them into its
jitted programs as arguments, so they are never compiled into the
executable as constants, and one compiled engine serves any tables of the
same tree, shapes and dtypes (weighted clique's per-query weights).
``score_children`` performs *targeted expansion*: it returns ``NEG``
priority for any (state, action) that must not be created,
so irrelevant subgraphs are never materialized (contrast: Arabesque's
exhaustive expansion + post-filter, implemented in
:mod:`repro.core.exhaustive` as the baseline).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

NEG = jnp.iinfo(jnp.int32).min  # "-inf" for int32 keys


@dataclasses.dataclass(frozen=True)
class SubgraphComputation:
    """A batched top-k subgraph-discovery computation."""

    name: str
    state_width: int   # S: int32 words per subgraph state
    num_actions: int   # A: action space (e.g. N vertices)

    # (tables) -> (states [n0, S], prio [n0], ub [n0]); traced once per
    # engine (jitted), so n0 is fixed by the computation
    init_frontier: Callable[[Any],
                            Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]

    # (states [B, S], tables) -> (child_prio [B, A], child_ub [B, A]);
    # NEG = not expandable
    score_children: Callable[[jnp.ndarray, Any],
                             Tuple[jnp.ndarray, jnp.ndarray]]

    # (parent_states [M, S], actions [M], tables) -> child states [M, S]
    materialize: Callable[[jnp.ndarray, jnp.ndarray, Any], jnp.ndarray]

    # (states [B, S], tables) -> result keys [B] (NEG when not relevant)
    result_key: Callable[[jnp.ndarray, Any], jnp.ndarray]

    # (states [B, S], tables) -> result-space upper bound [B]
    upper_bound: Callable[[jnp.ndarray, Any], jnp.ndarray]

    # pretty-printer for result states (host-side)
    describe: Optional[Callable] = None

    # pytree of device arrays the callbacks above read (the last argument
    # of each); the engine passes it into its jitted programs.  These are
    # the defaults: a query may run on other tables of the same tree,
    # shapes and dtypes (Engine.start)
    tables: Any = ()

    def __post_init__(self):
        if self.state_width <= 0:
            raise ValueError(
                f"{self.name}: state_width must be positive, "
                f"got {self.state_width}")
        if self.num_actions <= 0:
            raise ValueError(
                f"{self.name}: num_actions must be positive, "
                f"got {self.num_actions}")


def from_pointwise(name: str,
                   state_width: int,
                   num_actions: int,
                   init_frontier,        # (tables) -> (states, prio, ub)
                   expandable=None,      # (state [S], action, tables) -> bool
                   child_priority=None,  # (state [S], action, tables) -> int32
                   child_ub=None,        # (state [S], action, tables) -> int32
                   *,
                   materialize_one,      # (state [S], action, tables) -> [S]
                   relevant,             # (state [S], tables) -> bool
                   result_key_one,       # (state [S], tables) -> int32
                   upper_bound_one,      # (state [S], tables) -> int32
                   describe=None, tables=(),
                   score_children=None) -> SubgraphComputation:
    """Succinct per-subgraph API (the paper's Listing-1 style), vmapped.

    Users write scalar functions over a single state (plus the shared
    ``tables``); this adapter builds the batched computation via
    ``jax.vmap``.  A batched ``score_children`` (the field of
    :class:`SubgraphComputation`) replaces the three scalar scorers
    ``expandable``, ``child_priority`` and ``child_ub``: child scoring is
    the hot loop, and a computation may fuse it over the whole ``[B, A]``
    grid (:mod:`repro.core.weighted_clique`) while keeping the rest
    scalar.  The fused batched path (e.g. :mod:`repro.core.clique`) is
    preferred for hot computations.
    """
    if any((f is None) != (score_children is not None)
           for f in (expandable, child_priority, child_ub)):
        raise ValueError(f"{name}: give either expandable, child_priority "
                         "and child_ub, or a batched score_children")
    actions = jnp.arange(num_actions, dtype=jnp.int32)

    def pointwise_score_children(states, t):
        def per_state(s):
            def per_action(a):
                ok = expandable(s, a, t)
                return (jnp.where(ok, child_priority(s, a, t), NEG),
                        jnp.where(ok, child_ub(s, a, t), NEG))
            return jax.vmap(per_action)(actions)
        return jax.vmap(per_state)(states)

    def materialize(states, acts, t):
        return jax.vmap(lambda s, a: materialize_one(s, a, t))(states, acts)

    def result_key(states, t):
        def one(s):
            return jnp.where(relevant(s, t), result_key_one(s, t), NEG)
        return jax.vmap(one)(states)

    def upper_bound(states, t):
        return jax.vmap(lambda s: upper_bound_one(s, t))(states)

    return SubgraphComputation(
        name=name, state_width=state_width, num_actions=num_actions,
        init_frontier=init_frontier,
        score_children=score_children or pointwise_score_children,
        materialize=materialize, result_key=result_key,
        upper_bound=upper_bound, describe=describe, tables=tables)
