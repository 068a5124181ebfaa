"""Batched prioritized subgraph-expansion engine (paper Algorithm 1, TPU form).

One engine *super-step* replaces the paper's per-subgraph loop iteration:

1. **dequeue** the ``B`` highest-priority states from the device pool
   (``jax.lax.top_k`` — the priority queue's ``remove_max``, B-wide);
2. **result insertion** — merge relevant dequeued states into the top-k
   result set (Alg. 1 lines 6-10);
3. **pruning** — the k-th result key is the dominance threshold; dequeued
   states with ``upper_bound < threshold`` are dropped (line 11), candidate
   children with ``child_ub < threshold`` are never materialized (line 15);
4. **targeted expansion** — ``score_children`` yields priorities for the
   valid (state, action) grid only (line 13); parents are expanded greedily
   in priority order while their total child count fits the materialization
   budget ``M`` — parents that don't fit are *re-inserted unexpanded*, so no
   child is ever lost (completeness);
5. **insert** — pool ∪ children ∪ unexpanded parents are merge-sorted by
   priority; the top ``C`` stay on device, the rest exit as a fixed-size
   overflow block for the virtual priority queue to spill.

Distribution: :func:`make_sharded_bound_sync` builds the one collective the
distributed engine needs — an all-gather of per-shard result keys so every
shard prunes against the *global* k-th best (DESIGN.md §4).  The whole
super-step body (``_step_impl``) takes an optional ``bound_sync`` hook, so
:class:`repro.distributed.ShardedEngine` runs the identical code per shard
inside ``shard_map`` — the single-device :class:`Engine` is exactly the
1-shard specialization (DESIGN.md §11).

Macro-stepping (DESIGN.md §13): with ``EngineConfig.steps_per_sync = T > 1``
the engine fuses up to ``T`` super-steps into one jitted
``jax.lax.while_loop`` over ``_step_impl`` (``_macro_impl``), accumulating
stats and overflow in a fixed-capacity on-device buffer, so the host↔device
round-trip — ``device_get`` of the stats, Python dispatch, the overflow
ship-out — is paid once per *macro*-step instead of once per super-step.
The loop early-exits back to the host exactly when host work is due: the
pool dips under the ``C/2`` refill watermark while spill exists, the
overflow accumulator cannot fit another block, or the pool drains.  The
macro jit donates the pool buffers on backends that support donation, so
the ``C×S`` pool is updated in place instead of copied every step.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial, wraps
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .api import NEG, SubgraphComputation
from .vpq import VirtualPriorityQueue
from repro.obs import NOOP, Observability

# EngineState counters checkpointed verbatim (DESIGN.md §15)
_CKPT_SCALARS = ("steps", "candidates", "expanded", "pruned", "refilled",
                 "syncs", "host_syncs", "threshold", "pool_occupancy",
                 "done")


def named_program(name: str, fn):
    """``fn`` under the name ``name``.  ``jax.jit`` names the device
    program after the function it wraps (``jit_<name>``), and that is the
    name the profiler's ``XLA Modules`` line and the compile log show;
    the computation itself is unchanged."""
    @wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return program


def donatable_pool_argnums():
    """Pool-buffer argnums the macro-step jit may donate (DESIGN.md §13).

    The pool arrays (args 0-2: ``pool_states``/``pool_prio``/``pool_ub``,
    ``C×S`` + 2×``C``) are pure state-in/state-out, so donation lets XLA
    update them in place instead of copying every macro-step.  CPU has no
    donation support (XLA warns and copies anyway), so donate only where
    it is implemented.
    """
    return (0, 1, 2) if jax.default_backend() in ("gpu", "tpu") else ()


@dataclasses.dataclass
class EngineConfig:
    k: int = 1                    # result set size
    batch: int = 64               # B: states dequeued per super-step
    pool_capacity: int = 4096     # C: device-resident priority pool slots
    max_children: Optional[int] = None  # M: materialization budget (>= A)
    max_steps: int = 100_000
    spill: str = "host"           # VPQ backing: "host" | "disk" | "none"
    spill_dir: Optional[str] = None
    # device-mesh sharding (DESIGN.md §11): number of frontier shards.  The
    # single-device Engine ignores it; repro.distributed.ShardedEngine
    # seed-partitions the frontier over this many devices, with batch /
    # pool_capacity / max_children read as *per-shard* shapes.  Complete
    # runs are byte-identical for any shard count (parity-tested), but
    # budget-truncated runs are not, so like batch/pool_capacity — and
    # unlike the per-step-identical kernel knobs below — it enters the
    # service result-cache key.
    shards: int = 1
    # macro-stepping (DESIGN.md §13): number of super-steps fused into one
    # jitted while_loop between host syncs.  1 (default) preserves the
    # classic one-jit-call-per-step behavior; T > 1 amortizes dispatch /
    # device_get latency over T steps.  Complete runs are byte-identical
    # for any T (parity-tested) — like the kernel knobs, and unlike
    # batch/pool_capacity, it is excluded from the service result-cache
    # key; budget-truncated runs stop at the same step count for any T
    # (the macro loop is capped to the remaining budget) but may differ
    # in spill-run tie order.
    steps_per_sync: int = 1
    # capacity (entries) of the on-device overflow accumulator used by the
    # fused loop; None sizes it to steps_per_sync * (B + M) — enough that
    # it can never fill mid-macro-step.  Smaller values trade memory for
    # earlier syncs (the loop exits when the next block might not fit);
    # values below B + M are raised to B + M.
    overflow_accum: Optional[int] = None
    # staleness-tolerant bound exchange (DESIGN.md §14): number of inner
    # super-steps the sharded engine runs between §4 `bound_sync`
    # all-gathers.  Between exchanges every shard prunes against
    # max(last-exchanged global bound, its own fresh local k-th best) —
    # both are lower bounds on the fresh global k-th best, so the interim
    # threshold is only ever *looser* than the fresh one and complete
    # runs stay byte-identical for any value (property-tested in
    # tests/test_stale_bound.py), while collectives drop by a factor of
    # K.  Like steps_per_sync it is excluded from the service
    # result-cache key (budget truncation still lands on the same step
    # count) but included in the engine-reuse key.  The single-device
    # Engine has no collective to amortize and ignores it.  K > 1
    # implies macro-stepping: the sharded engine raises the fused length
    # to the next multiple of K so every fused call ends on an exchange
    # boundary, and clamps K so a full K-step segment always fits the
    # overflow accumulator.
    sync_every: int = 1
    # debug/test hook (tests/test_stale_bound.py): record, per fused
    # inner step, the threshold each shard actually pruned with and the
    # fresh global bound a per-step exchange would have produced
    # (surfaced via EngineResult.per_shard["bound_used"/"bound_fresh"]).
    # Costs one extra all-gather per stale step — never enable outside
    # tests.
    record_bound_trace: bool = False
    # durable runs (DESIGN.md §15): with checkpoint_every = N > 0 and a
    # checkpoint_dir, Engine.run()/ShardedEngine.run() persist the full
    # engine state (pool, results, VPQ runs, counters) through
    # CheckpointManager's atomic-commit protocol at the first host-sync
    # boundary every >= N steps, and Engine.resume() reconstructs an
    # EngineState whose continued run is byte-identical to an
    # uninterrupted one (same top-k, same step trajectory — the same
    # invariant discipline as shards/T/K, crash-proved in
    # tests/test_fault_injection.py).  Checkpoints are pure observers of
    # host-sync state, so like the kernel knobs both fields are excluded
    # from the service result-cache key (but included in the engine-reuse
    # key: tasks sharing an engine share its checkpoint policy).
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None
    # kernel-path knobs (DESIGN.md §10): a declarative record consumed at
    # computation-construction time (service.api.compile_request reads
    # them when calling make_*_computation) — NOT by the engine loop,
    # which is kernel-agnostic.  Setting them here does not retrofit a
    # computation you already built; direct Engine callers must pass the
    # knobs to make_*_computation themselves.  Both settings leave results
    # byte-identical (parity-tested), so they are also excluded from the
    # service result-cache key.
    use_pallas: bool = False      # score via the Pallas masked-intersection
    interpret: Optional[bool] = None  # None = auto-detect backend
    # observability (DESIGN.md §16): observe=True routes the engine's
    # metrics/spans into a live repro.obs.Observability instead of the
    # process-global no-op.  A pure observer like checkpointing — results
    # are byte-identical either way (parity-tested across shard counts
    # and T in tests/test_obs.py) — so it is excluded from the service
    # result-cache key but included in the engine-reuse key.
    # ``observability`` optionally injects a shared instance (the service
    # layer passes its own so per-request and per-engine telemetry land
    # in one registry); None with observe=True creates a private one.
    observe: bool = False
    observability: Optional[object] = None


@dataclasses.dataclass
class EngineResult:
    result_states: np.ndarray     # [k, S]
    result_keys: np.ndarray       # [k] (NEG = empty slot)
    steps: int
    candidates: int               # subgraphs materialized (paper metric 1)
    expanded: int                 # subgraphs actually expanded
    pruned: int                   # dequeued states dropped by dominance
    spilled: int
    refilled: int
    rebalanced: int = 0           # spilled entries moved across shards (§11)
    late_pruned: int = 0          # dominated entries dropped at VPQ refill
    # bound-exchange collectives actually run (§14): ceil(steps /
    # sync_every) per fused call for the sharded engine; 0 for the
    # single-device engine, which computes its threshold locally and
    # never talks to another shard
    syncs: int = 0
    host_syncs: int = 0           # host↔device round-trips (== steps at T=1)
    per_shard: Optional[dict] = None  # ShardedEngine: per-shard stat lists


@dataclasses.dataclass
class EngineState:
    """Resumable per-query engine state (DESIGN.md §9).

    One super-step maps ``EngineState -> EngineState``; :meth:`Engine.run`
    is just a loop over :meth:`Engine.step`, which lets an external
    scheduler (``repro.service.scheduler``) interleave super-steps of many
    live queries on one device without any engine changes.
    """

    pool_states: jnp.ndarray      # [C, S]
    pool_prio: jnp.ndarray        # [C]
    pool_ub: jnp.ndarray          # [C]
    result_states: jnp.ndarray    # [k, S]
    result_keys: jnp.ndarray      # [k]
    vpq: VirtualPriorityQueue
    # this query's device tables, the argument of every program its search
    # runs: the computation's own, or per-query ones of the same tree,
    # shapes and dtypes (weighted clique's weights), so the engine's
    # compiled programs serve them without a new trace
    tables: Any
    steps: int = 0
    candidates: int = 0
    expanded: int = 0
    pruned: int = 0
    refilled: int = 0
    syncs: int = 0                # bound-exchange collectives (0 unsharded)
    host_syncs: int = 0           # host↔device round-trips taken so far
    threshold: int = int(NEG)
    pool_occupancy: int = 0
    done: bool = False            # pool and VPQ both drained


def merge_topk(states: jnp.ndarray, keys: jnp.ndarray, k: int):
    """Canonical top-k selection over result candidates: key descending,
    ties broken by the state words lexicographically ascending (signed
    int32 order, word 0 most significant), duplicates collapsed.

    Candidates may contain the same (state, key) pair more than once — a
    deferred parent re-enters the pool and contributes its result key again
    on re-dequeue, and per-shard result sets can both have seen a state the
    rebalancer moved.  All but the first occurrence of a duplicate are
    demoted to empty, so one state can never
    occupy two result slots (which would both displace the true k-th result
    and tighten the dominance threshold unsoundly).

    Dedup plus the deterministic tie-break make the result set a pure
    function of the *set* of discovered (state, key) pairs — insertion
    order and multiplicity cannot change the outcome — which is what lets
    a sharded run (any shard count, any interleaving) reproduce the
    single-device result set byte-for-byte (DESIGN.md §11).  States in
    empty slots (key == NEG) are zeroed so they too are byte-stable.

    The order is computed pairwise: candidates are few (``k + B`` per
    super-step, ``shards * k`` in the bound exchange) while states are
    wide (``S = 2W + 2`` words for clique), so comparing every pair at its
    first differing word costs O(n²·S) elementwise work and one 1-key
    sort.  XLA fuses the compare into the reduction, so no ``[n, n, S]``
    array is formed; the ``[n, n]`` intermediates are what grows with k.
    A lexicographic multi-key sort would take one sort operand per state
    word, and its compile time grows with ``S``.  A prefix-doubling rank
    (O(S·n·log n·log S) in batched sorts) compiled and ran slower at the
    sizes the engine sees (PERF.md, Findings).
    """
    n, s = states.shape
    # first differing word of every pair of rows (s where the rows match)
    word = jnp.arange(s, dtype=jnp.int32)
    first = jnp.min(jnp.where(states[:, None, :] != states[None, :, :],
                              word, s), axis=-1)                 # [n, n]
    same = first == s
    # at[i, j] = states[i, first[i, j]]; first is symmetric, so at.T holds
    # row j's word at the same position and `at < at.T` is "row i
    # lexicographically before row j" (signed int32 order)
    at = jnp.take_along_axis(states, jnp.minimum(first, s - 1), axis=1)
    same_key = keys[:, None] == keys[None, :]
    precedes = (keys[:, None] > keys[None, :]) | (same_key & (at < at.T))
    # of identical (state, key) pairs only the first occurrence is live
    row = jnp.arange(n)
    dup = jnp.any(same & same_key & (row[None, :] < row[:, None]), axis=1)
    live = (keys > NEG) & ~dup
    # live pairs are distinct, so their ranks are 0..live-1 with no ties
    rank = jnp.where(live, jnp.sum(live[:, None] & precedes, axis=0), n)
    top = jnp.argsort(rank)[:k]
    top_keys = jnp.where(rank[top] < n, keys[top], NEG)
    top_states = jnp.where((top_keys > NEG)[:, None], states[top], 0)
    return top_states, top_keys


class Engine:
    """Runs one :class:`SubgraphComputation` to completion (or stepwise)."""

    def __init__(self, comp: SubgraphComputation, config: EngineConfig):
        self.comp = comp
        self.cfg = config
        a = comp.num_actions
        self.M = max(config.max_children or 0, a)
        self.B = config.batch
        self.C = config.pool_capacity
        self.S = comp.state_width
        self.k = config.k
        self.T = max(1, config.steps_per_sync)
        # overflow-accumulator capacity: one super-step's overflow block is
        # exactly B + M entries (the merge-sort insert over C + M + B rows
        # keeps C), so T blocks can never overflow the default sizing
        self.acc_cap = max(config.overflow_accum or self.T * (self.B + self.M),
                           self.B + self.M)
        # the computation's default tables; a query may start on its own
        self.tables = comp.tables
        self._init = jax.jit(named_program("discovery_init",
                                           comp.init_frontier))
        self._step = jax.jit(named_program("discovery_step",
                                           self._step_impl))
        self._insert = jax.jit(named_program("discovery_insert",
                                             self._insert_impl))
        if self.T > 1:
            self._macro = jax.jit(named_program("discovery_macro",
                                                self._macro_impl),
                                  donate_argnums=donatable_pool_argnums())
        # observability (DESIGN.md §16): metric handles are resolved once
        # here — the step loop touches the metric objects directly, never
        # the registry.  With observe off every handle is the shared
        # null metric and self._span returns the shared null span.
        if config.observe:
            self.obs = config.observability or Observability()
        else:
            self.obs = NOOP
        obs = self.obs
        self._span = obs.tracer.span
        self._m_steps = obs.counter(
            "engine_steps_total", "engine super-steps completed")
        self._m_host_syncs = obs.counter(
            "engine_host_syncs_total", "host-device round-trips")
        self._m_candidates = obs.counter(
            "engine_candidates_total", "subgraphs materialized")
        self._m_expanded = obs.counter(
            "engine_expanded_total", "subgraphs expanded")
        self._m_pruned = obs.counter(
            "engine_pruned_total", "dequeued states dropped by dominance")
        self._m_refilled = obs.counter(
            "engine_refilled_total", "pool entries refilled from spill")
        self._g_occupancy = obs.gauge(
            "engine_pool_occupancy", "live device-pool entries")
        self._g_threshold = obs.gauge(
            "engine_threshold", "current dominance threshold (k-th key)")

    # ------------------------------------------------------------------ step
    def _step_impl(self, pool_states, pool_prio, pool_ub,
                   result_states, result_keys, tables, bound_sync=None):
        """One super-step.  ``tables`` is the query's device tables
        (``EngineState.tables``), an argument so the graph is never
        compiled in.
        ``bound_sync`` (None for the single-device engine) maps the local
        result keys to the pruning threshold; the sharded engine passes
        :func:`make_sharded_bound_sync`'s collective so every shard prunes
        against the global k-th best (DESIGN.md §11).
        """
        comp, B, M, C, k = self.comp, self.B, self.M, self.C, self.k
        A = comp.num_actions

        # 1. dequeue top-B
        prio_b, idx_b = jax.lax.top_k(pool_prio, B)
        valid_b = prio_b > NEG
        states_b = pool_states[idx_b]
        ub_b = pool_ub[idx_b]
        pool_prio = pool_prio.at[idx_b].set(NEG)

        # 2. result insertion (Alg. 1 lines 6-10), canonical tie-break
        rkey_b = jnp.where(valid_b, comp.result_key(states_b, tables), NEG)
        merged_keys = jnp.concatenate([result_keys, rkey_b])
        merged_states = jnp.concatenate([result_states, states_b])
        result_states, result_keys = merge_topk(merged_states, merged_keys, k)

        # 3. dominance threshold (the k-th entry; NEG while R not full);
        #    under a bound_sync this is the *global* k-th best
        if bound_sync is None:
            threshold = jnp.where(result_keys[k - 1] > NEG,
                                  result_keys[k - 1], NEG)
        else:
            threshold = bound_sync(result_states, result_keys)
        expand_b = valid_b & (ub_b >= threshold)
        pruned = jnp.sum(valid_b & ~expand_b)

        # 4. targeted expansion: score the [B, A] child grid
        child_prio, child_ub = comp.score_children(states_b, tables)
        keep = expand_b[:, None] & (child_prio > NEG) & (child_ub >= threshold)

        # greedy parent admission: expand parents (already sorted by priority)
        # while cumulative child count fits M; the rest re-enter the pool.
        counts = jnp.sum(keep, axis=1)
        fits = jnp.cumsum(counts) <= M
        admitted = expand_b & fits
        deferred = valid_b & expand_b & ~fits          # re-insert unexpanded
        keep = keep & admitted[:, None]

        flat_prio = jnp.where(keep, child_prio, NEG).reshape(B * A)
        top_cp, top_ci = jax.lax.top_k(flat_prio, M)
        sel_valid = top_cp > NEG
        sel_parent = top_ci // A
        sel_action = (top_ci % A).astype(jnp.int32)
        child_states = comp.materialize(states_b[sel_parent], sel_action,
                                       tables)
        child_states = jnp.where(sel_valid[:, None], child_states, 0)
        child_ub_sel = jnp.where(
            sel_valid, child_ub.reshape(B * A)[top_ci], NEG)
        child_prio_sel = jnp.where(sel_valid, top_cp, NEG)

        # 5. merge-sort insert: pool ∪ children ∪ deferred parents
        def_prio = jnp.where(deferred, prio_b, NEG)
        cat_prio = jnp.concatenate([pool_prio, child_prio_sel, def_prio])
        cat_ub = jnp.concatenate([pool_ub, child_ub_sel,
                                  jnp.where(deferred, ub_b, NEG)])
        cat_states = jnp.concatenate([pool_states, child_states, states_b])
        order = jnp.argsort(cat_prio, descending=True)
        pool_prio = cat_prio[order[:C]]
        pool_ub = cat_ub[order[:C]]
        pool_states = cat_states[order[:C]]
        over = order[C:]
        overflow = (cat_states[over], cat_prio[over], cat_ub[over])

        stats = dict(
            dequeued=jnp.sum(valid_b).astype(jnp.int32),
            expanded=jnp.sum(admitted).astype(jnp.int32),
            created=jnp.sum(sel_valid).astype(jnp.int32),
            pruned=pruned.astype(jnp.int32),
            pool_occupancy=jnp.sum(pool_prio > NEG).astype(jnp.int32),
            threshold=threshold,
        )
        return (pool_states, pool_prio, pool_ub, result_states, result_keys,
                overflow, stats)

    # ------------------------------------------------------------ macro-step
    def _macro_impl(self, pool_states, pool_prio, pool_ub,
                    result_states, result_keys, tables, t_max, vpq_nonempty,
                    occ0, bound_sync=None, any_reduce=None, sync_every=1,
                    stale_sync=None, record_bounds=False):
        """Up to ``t_max`` fused super-steps in one ``lax.while_loop``
        (DESIGN.md §13).  Per-step overflow blocks land in a fixed
        ``[acc_cap, S]`` on-device accumulator — each block is written at
        the valid-entry watermark ``w`` and, because blocks exit the
        merge-sort insert sorted by descending priority, their valid
        entries are a prefix, so advancing ``w`` by the valid count packs
        the accumulator densely and the host ships exactly ``acc[:w]``.

        The loop hands control back to the host exactly when host work is
        due, i.e. it continues only while (a) steps remain, (b) the next
        overflow block (segment of blocks under ``sync_every > 1``) is
        guaranteed to fit, (c) the pool is non-empty, and (d) no refill is
        possible — the pool is at or above the ``C//2`` watermark, or
        nothing is spilled (VPQ empty at entry and accumulator empty).
        (d) reproduces the unfused refill cadence step-for-step: the fused
        engine syncs at the first step whose unfused counterpart would
        have refilled.

        ``bound_sync`` / ``any_reduce`` are the sharded engine's hooks:
        the first is the §4 threshold collective, the second reduces
        per-shard continue/stop votes to a global decision so all shards
        leave the loop together and the in-loop collectives stay aligned.
        The continue flag is computed in the loop *body* and carried, so
        the ``while_loop`` cond stays collective-free.

        ``sync_every = K > 1`` selects the staleness-tolerant cadence
        (DESIGN.md §14): each loop iteration is one *segment* — a head
        step that runs the fresh ``bound_sync`` exchange followed by up
        to ``K - 1`` tail steps whose threshold is
        ``stale_sync(last exchange, local result keys)``, a bound that is
        only ever *looser* than the fresh one (so pruning stays sound and
        complete runs byte-identical) — and the continue/stop votes are
        reduced once per segment instead of once per step, so collectives
        drop by a factor of K.  Tail steps run unconditionally (a drained
        shard pads with no-op steps until the boundary) so every shard
        reaches each collective together.  ``record_bounds`` additionally
        journals, per inner step, the threshold actually used and the
        fresh global bound a per-step exchange would have produced
        (``stats["bound_used"/"bound_fresh"]``, valid prefix ``steps``) —
        the §14 staleness invariant made observable for tests.
        """
        if sync_every <= 1 and not record_bounds:
            return self._macro_flat(
                pool_states, pool_prio, pool_ub, result_states, result_keys,
                tables, t_max, vpq_nonempty, occ0, bound_sync, any_reduce)
        return self._macro_segmented(
            pool_states, pool_prio, pool_ub, result_states, result_keys,
            tables, t_max, vpq_nonempty, occ0, bound_sync, any_reduce,
            max(1, sync_every), stale_sync, record_bounds)

    def _cont_flag(self, seg_blocks, vpq_nonempty, any_reduce,
                   t_max, t_next, w, occ):
        """Continue/stop decision shared by both macro variants:
        ``seg_blocks`` is the number of overflow blocks the next loop
        iteration may produce (1 flat, K segmented)."""
        C, cap = self.C, self.acc_cap
        room = (w + seg_blocks * (self.B + self.M)) <= cap
        active = occ > 0
        low = occ < (C // 2)
        refillable = vpq_nonempty | (w > 0)
        if any_reduce is None:
            need_host = jnp.logical_not(room) | (low & refillable)
            cont = jnp.logical_not(need_host) & active
        else:
            # per-shard votes -> one global decision: stop when ANY
            # shard needs host service (its own refill moment or a
            # full accumulator), keep going while ANY shard is active;
            # refill-ability is global because the host rebalancer can
            # move any shard's spill to any starving shard
            need_host = jnp.logical_not(room) | \
                (low & any_reduce(refillable))
            cont = jnp.logical_not(any_reduce(need_host)) & \
                any_reduce(active)
        return (t_next < t_max) & cont

    def _fused_step(self, ps, pp, pu, rs, rk, tables, acc_s, acc_p, acc_u,
                    w, sums, sync_fn):
        """One inner super-step plus overflow-accumulator/stat packing —
        the body both macro variants repeat."""
        ps, pp, pu, rs, rk, (o_s, o_p, o_u), stats = self._step_impl(
            ps, pp, pu, rs, rk, tables, bound_sync=sync_fn)
        cnt = jnp.sum(o_p > NEG).astype(jnp.int32)
        acc_s = jax.lax.dynamic_update_slice(acc_s, o_s, (w, 0))
        acc_p = jax.lax.dynamic_update_slice(acc_p, o_p, (w,))
        acc_u = jax.lax.dynamic_update_slice(acc_u, o_u, (w,))
        w = w + cnt
        sums = {name: sums[name] + stats[name]
                for name in ("expanded", "created", "pruned")}
        return ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums, stats

    def _macro_flat(self, pool_states, pool_prio, pool_ub,
                    result_states, result_keys, tables, t_max, vpq_nonempty,
                    occ0, bound_sync, any_reduce):
        """The ``sync_every == 1`` macro loop: one step per iteration, the
        §4 exchange (when sharded) and the exit vote every inner step."""
        S, cap = self.S, self.acc_cap
        cont_flag = partial(self._cont_flag, 1, vpq_nonempty, any_reduce,
                            t_max)

        def body(carry):
            (t, ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums, _occ,
             _thr, _cont) = carry
            (ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums, stats) = \
                self._fused_step(ps, pp, pu, rs, rk, tables, acc_s, acc_p,
                                 acc_u, w, sums, bound_sync)
            occ = stats["pool_occupancy"]
            return (t + 1, ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w,
                    sums, occ, stats["threshold"],
                    cont_flag(t + 1, w, occ))

        zero = jnp.int32(0)
        carry = (zero, pool_states, pool_prio, pool_ub,
                 result_states, result_keys,
                 jnp.zeros((cap, S), jnp.int32),
                 jnp.full((cap,), NEG, jnp.int32),
                 jnp.full((cap,), NEG, jnp.int32),
                 zero, dict(expanded=zero, created=zero, pruned=zero),
                 jnp.asarray(occ0, jnp.int32), jnp.int32(NEG),
                 jnp.asarray(True))  # the first inner step always runs
        (t, ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums, occ, thr,
         _cont) = jax.lax.while_loop(lambda c: c[-1], body, carry)
        stats = dict(sums, steps=t, spill_count=w, pool_occupancy=occ,
                     threshold=thr)
        return ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, stats

    def _macro_segmented(self, pool_states, pool_prio, pool_ub,
                         result_states, result_keys, tables, t_max,
                         vpq_nonempty, occ0, bound_sync, any_reduce,
                         sync_every, stale_sync, record_bounds):
        """The ``sync_every = K > 1`` macro loop (DESIGN.md §14): each
        iteration runs one K-step segment — fresh exchange at the head,
        stale-bound tail, one vote at the boundary.  Collective-free when
        ``bound_sync is None`` (single-device with ``record_bound_trace``):
        the head threshold is then the local k-th best and the stale/fresh
        traces coincide by construction."""
        S, cap, K, k = self.S, self.acc_cap, sync_every, self.k
        cont_flag = partial(self._cont_flag, K, vpq_nonempty, any_reduce,
                            t_max)
        if stale_sync is None:
            stale_sync = make_stale_bound_sync(k)

        def fresh_fn(srs, srk):   # what a per-step exchange would produce
            if bound_sync is not None:
                return bound_sync(srs, srk)
            return jnp.where(srk[k - 1] > NEG, srk[k - 1], NEG)

        def body(carry):
            (t, ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums, _occ,
             _stale, _cont, tr_u, tr_f) = carry
            # segment head: the fresh §4 exchange becomes this segment's
            # carried global bound
            (ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums, stats) = \
                self._fused_step(ps, pp, pu, rs, rk, tables, acc_s, acc_p,
                                 acc_u, w, sums, bound_sync)
            stale = stats["threshold"]
            occ = stats["pool_occupancy"]
            if record_bounds:
                tr_u = tr_u.at[t].set(stale)
                tr_f = tr_f.at[t].set(stale)
            t = t + 1

            def tail_step(_i, c):
                (t_i, ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums,
                 _o, tr_u, tr_f) = c
                box = {}

                def sync_fn(srs, srk):
                    used = stale_sync(stale, srk)
                    box["used"] = used
                    if record_bounds:
                        box["fresh"] = fresh_fn(srs, srk)
                    return used

                (ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums,
                 stats) = self._fused_step(ps, pp, pu, rs, rk, tables,
                                           acc_s, acc_p, acc_u, w, sums,
                                           sync_fn)
                if record_bounds:
                    tr_u = tr_u.at[t_i].set(box["used"])
                    tr_f = tr_f.at[t_i].set(box["fresh"])
                return (t_i + 1, ps, pp, pu, rs, rk, acc_s, acc_p, acc_u,
                        w, sums, stats["pool_occupancy"], tr_u, tr_f)

            # tail steps run unconditionally to the segment boundary (or
            # the step budget) so every shard meets the next collective;
            # a drained shard's extra steps dequeue nothing and are no-ops
            n_tail = jnp.minimum(jnp.int32(K - 1), t_max - t)
            (t, ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums, occ,
             tr_u, tr_f) = jax.lax.fori_loop(
                jnp.int32(0), n_tail, tail_step,
                (t, ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums, occ,
                 tr_u, tr_f))
            return (t, ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums,
                    occ, stale, cont_flag(t, w, occ), tr_u, tr_f)

        zero = jnp.int32(0)
        trace = jnp.full((self.T,), NEG, jnp.int32)
        carry = (zero, pool_states, pool_prio, pool_ub,
                 result_states, result_keys,
                 jnp.zeros((cap, S), jnp.int32),
                 jnp.full((cap,), NEG, jnp.int32),
                 jnp.full((cap,), NEG, jnp.int32),
                 zero, dict(expanded=zero, created=zero, pruned=zero),
                 jnp.asarray(occ0, jnp.int32), jnp.int32(NEG),
                 jnp.asarray(True),   # the first segment always runs
                 trace, trace)
        (t, ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, w, sums, occ, stale,
         _cont, tr_u, tr_f) = jax.lax.while_loop(
            lambda c: c[13], body, carry)
        # report the *exchanged* bound (replicated across shards) as the
        # macro threshold: the host's late-pruning cutoff must be a global
        # lower bound, and stale is exactly that (§14 soundness)
        stats = dict(sums, steps=t, spill_count=w, pool_occupancy=occ,
                     threshold=stale)
        if record_bounds:
            stats["bound_used"] = tr_u
            stats["bound_fresh"] = tr_f
        return ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, stats

    # ---------------------------------------------------------------- insert
    def _insert_impl(self, pool_states, pool_prio, pool_ub,
                     new_states, new_prio, new_ub):
        C = self.C
        cat_prio = jnp.concatenate([pool_prio, new_prio])
        cat_ub = jnp.concatenate([pool_ub, new_ub])
        cat_states = jnp.concatenate([pool_states, new_states])
        order = jnp.argsort(cat_prio, descending=True)
        over = order[C:]
        return (cat_states[order[:C]], cat_prio[order[:C]], cat_ub[order[:C]],
                cat_states[over], cat_prio[over], cat_ub[over])

    # ----------------------------------------------------------------- start
    def _place(self, tables):
        """A query's ``tables`` on the device (the engine's own when
        None); leaves already there are not copied."""
        if tables is None:
            return self.tables
        return jax.tree.map(jnp.asarray, tables)

    def start(self, tables=None) -> EngineState:
        """Seed the frontier and return a resumable :class:`EngineState`
        that searches with ``tables`` (default: the computation's)."""
        with self._span("engine.start"):
            return self._start_impl(self._place(tables))

    def _start_impl(self, tables) -> EngineState:
        cfg, S, C, k = self.cfg, self.S, self.C, self.k
        vpq = VirtualPriorityQueue(
            state_width=S, backend=cfg.spill, spill_dir=cfg.spill_dir,
            obs=self.obs)

        states0, prio0, ub0 = self._init(tables)
        n0 = states0.shape[0]

        pool_states = jnp.zeros((C, S), jnp.int32)
        pool_prio = jnp.full((C,), NEG, jnp.int32)
        pool_ub = jnp.full((C,), NEG, jnp.int32)
        if n0 <= C:
            pool_states, pool_prio, pool_ub, os_, op_, ou_ = self._insert(
                pool_states, pool_prio, pool_ub, states0, prio0, ub0)
            vpq.maybe_push(np.asarray(os_), np.asarray(op_), np.asarray(ou_))
        else:  # more seeds than pool slots: top-C on device, rest spilled
            order = np.argsort(-np.asarray(prio0), kind="stable")
            states0, prio0, ub0 = (np.asarray(states0)[order],
                                   np.asarray(prio0)[order],
                                   np.asarray(ub0)[order])
            pool_states = jnp.asarray(states0[:C])
            pool_prio = jnp.asarray(prio0[:C])
            pool_ub = jnp.asarray(ub0[:C])
            vpq.maybe_push(states0[C:], prio0[C:], ub0[C:])

        return EngineState(
            pool_states=pool_states, pool_prio=pool_prio, pool_ub=pool_ub,
            result_states=jnp.zeros((k, S), jnp.int32),
            result_keys=jnp.full((k,), NEG, jnp.int32),
            vpq=vpq, tables=tables, candidates=int(n0),
            pool_occupancy=min(int(n0), C))

    # ------------------------------------------------------------------ step
    def step(self, st: EngineState, max_inner: Optional[int] = None
             ) -> EngineState:
        """Advance one engine step — a single super-step at
        ``steps_per_sync == 1``, else one fused *macro*-step of up to
        ``min(steps_per_sync, max_inner)`` super-steps (DESIGN.md §13).
        ``max_inner`` caps the fused super-step count so external step
        budgets (``max_steps``, the service ``step_budget``) truncate at
        exactly the same step count for any ``steps_per_sync``.  Updates
        ``st`` in place and returns it.
        """
        if self.T == 1:
            with self._span("engine.step"):
                # dispatch returns before the device runs; the wait is
                # where the host blocks on the step's stats
                with self._span("engine.dispatch"):
                    (st.pool_states, st.pool_prio, st.pool_ub,
                     st.result_states, st.result_keys, overflow,
                     stats) = self._step(
                        st.pool_states, st.pool_prio, st.pool_ub,
                        st.result_states, st.result_keys, st.tables)
                with self._span("engine.wait"):
                    stats = jax.tree.map(int, jax.device_get(stats))
                st.steps += 1
                st.host_syncs += 1
                st.expanded += stats["expanded"]
                st.candidates += stats["created"]
                st.pruned += stats["pruned"]
                st.threshold = stats["threshold"]
                with self._span("engine.fetch_overflow"):
                    st.vpq.maybe_push(*map(np.asarray, overflow))
                self._refill(st, stats["pool_occupancy"])
            self._after_step(st, 1, stats)
            return st

        t_cap = (self.T if max_inner is None
                 else max(1, min(self.T, int(max_inner))))
        with self._span("engine.step"):
            with self._span("engine.dispatch"):
                (st.pool_states, st.pool_prio, st.pool_ub,
                 st.result_states, st.result_keys, acc_s, acc_p, acc_u,
                 stats) = self._macro(
                    st.pool_states, st.pool_prio, st.pool_ub,
                    st.result_states, st.result_keys, st.tables,
                    np.int32(t_cap), len(st.vpq) > 0,
                    np.int32(st.pool_occupancy))
            with self._span("engine.wait"):
                stats = jax.tree.map(int, jax.device_get(stats))
            st.steps += stats["steps"]
            st.host_syncs += 1
            st.expanded += stats["expanded"]
            st.candidates += stats["created"]
            st.pruned += stats["pruned"]
            st.threshold = stats["threshold"]
            w = stats["spill_count"]
            if w:  # ship only the accumulator's valid prefix; none when dry
                with self._span("engine.fetch_overflow"):
                    st.vpq.maybe_push(np.asarray(acc_s)[:w],
                                      np.asarray(acc_p)[:w],
                                      np.asarray(acc_u)[:w])
            self._refill(st, stats["pool_occupancy"])
        self._after_step(st, stats["steps"], stats)
        return st

    def _after_step(self, st: EngineState, n_steps: int, stats: dict
                    ) -> None:
        """Record one step() call's metrics (no-op handles when off)."""
        self._m_steps.inc(n_steps)
        self._m_host_syncs.inc()
        self._m_expanded.inc(stats["expanded"])
        self._m_candidates.inc(stats["created"])
        self._m_pruned.inc(stats["pruned"])
        self._g_occupancy.set(st.pool_occupancy)
        self._g_threshold.set(st.threshold)

    # ---------------------------------------------------------------- refill
    def _refill(self, st: EngineState, occ: int) -> None:
        """Refill the pool from spill when under the C/2 watermark; sets
        ``pool_occupancy`` and ``done``."""
        C = self.C
        refilled_now = 0
        if occ < C // 2 and len(st.vpq):
            # refill from spill runs; entries dominated by the current
            # threshold are dropped at the VPQ (paper-style late pruning)
            with self._span("engine.refill"):
                r_states, r_prio, r_ub = st.vpq.pop_chunk(
                    C - occ, min_ub=st.threshold)
                if len(r_prio):
                    refilled_now = len(r_prio)
                    st.refilled += refilled_now
                    self._m_refilled.inc(refilled_now)
                    (st.pool_states, st.pool_prio, st.pool_ub,
                     os_, op_, ou_) = self._insert(
                        st.pool_states, st.pool_prio, st.pool_ub,
                        jnp.asarray(r_states), jnp.asarray(r_prio),
                        jnp.asarray(r_ub))
                    st.vpq.maybe_push(np.asarray(os_), np.asarray(op_),
                                      np.asarray(ou_))
        # refilled entries are live in the pool (their priorities are > NEG),
        # so a refill that drained the VPQ must not read as completion
        st.pool_occupancy = occ + refilled_now
        st.done = st.pool_occupancy == 0 and len(st.vpq) == 0

    # -------------------------------------------------------------- finalize
    def finalize(self, st: EngineState) -> EngineResult:
        """Close the VPQ and package the result set."""
        with self._span("engine.finalize"):
            st.vpq.close()
            return self._package(st)

    def _package(self, st: EngineState) -> EngineResult:
        return EngineResult(
            result_states=np.asarray(st.result_states),
            result_keys=np.asarray(st.result_keys),
            steps=st.steps, candidates=st.candidates, expanded=st.expanded,
            pruned=st.pruned, spilled=st.vpq.total_spilled,
            refilled=st.refilled, late_pruned=st.vpq.total_late_pruned,
            syncs=st.syncs, host_syncs=st.host_syncs)

    # ------------------------------------------------------- checkpointing
    def _ckpt_arrays(self, st: EngineState) -> dict:
        return dict(pool_states=st.pool_states, pool_prio=st.pool_prio,
                    pool_ub=st.pool_ub, result_states=st.result_states,
                    result_keys=st.result_keys)

    def save_checkpoint(self, mgr, st: EngineState,
                        blocking: bool = False) -> None:
        """Persist ``st`` through ``mgr``'s atomic-commit protocol
        (DESIGN.md §15).  The VPQ capture (array snapshots + hardlinks of
        disk run files) runs synchronously before this returns, so the
        engine may keep mutating — including deleting exhausted spill
        runs — while the leaf arrays flush on the writer thread.  Pure
        observer: saving never perturbs the step trajectory."""
        scalars = {name: getattr(st, name) for name in _CKPT_SCALARS}

        def capture(tmp_dir: str) -> dict:
            vpq = st.vpq.snapshot(os.path.join(tmp_dir, "vpq"))
            return {"kind": "engine", "scalars": scalars, "vpq": vpq}

        mgr.save(st.steps, self._ckpt_arrays(st), blocking=blocking,
                 capture=capture)

    def resume(self, source, step: Optional[int] = None,
               tables=None) -> EngineState:
        """Reconstruct an :class:`EngineState` from a committed checkpoint
        (a directory path or a :class:`CheckpointManager`); its continued
        run is byte-identical to an uninterrupted one given the ``tables``
        the checkpointed query searched with (default: the
        computation's; a checkpoint holds no tables).  Spill files
        referenced by the checkpoint are re-linked into the live spill
        dir (``cfg.spill_dir`` or a fresh temp dir), so the checkpoint
        remains restorable any number of times."""
        from repro.checkpoint.manager import CheckpointManager
        mgr = (source if isinstance(source, CheckpointManager)
               else CheckpointManager(source, obs=self.obs))
        manifest = mgr.read_manifest(step)
        step = manifest["step"]
        extra = manifest["extra"]
        if extra is None or extra.get("kind") != "engine":
            raise ValueError(
                f"step {step} in {mgr.dir} is not an engine checkpoint")
        like = {name: np.zeros(
            [int(s) for s in leaf["shape"]], np.dtype(leaf["dtype"]))
            for leaf in manifest["leaves"]
            for name in [leaf["name"]]}
        tree = mgr.restore(like, step=step)
        vpq = VirtualPriorityQueue.restore(
            extra["vpq"], os.path.join(mgr.path(step), "vpq"),
            spill_dir=self.cfg.spill_dir, obs=self.obs)
        return EngineState(
            pool_states=jnp.asarray(tree["pool_states"]),
            pool_prio=jnp.asarray(tree["pool_prio"]),
            pool_ub=jnp.asarray(tree["pool_ub"]),
            result_states=jnp.asarray(tree["result_states"]),
            result_keys=jnp.asarray(tree["result_keys"]),
            vpq=vpq, tables=self._place(tables), **extra["scalars"])

    # ------------------------------------------------------------------- run
    def run(self, progress_every: int = 0,
            resume: bool = False) -> EngineResult:
        """Run to completion (or ``max_steps``).  With
        ``cfg.checkpoint_every > 0`` and a ``cfg.checkpoint_dir``, the
        state is persisted at the first host-sync boundary every
        ``checkpoint_every`` steps; ``resume=True`` continues from the
        newest committed step there (fresh start if none committed)."""
        mgr = None
        if self.cfg.checkpoint_dir and (self.cfg.checkpoint_every > 0
                                        or resume):
            from repro.checkpoint.manager import CheckpointManager
            mgr = CheckpointManager(self.cfg.checkpoint_dir, obs=self.obs)
        st = None
        if resume and mgr is not None and mgr.latest_step() is not None:
            st = self.resume(mgr)
        if st is None:
            st = self.start()
        every = self.cfg.checkpoint_every
        last_ckpt = st.steps
        while not st.done and st.steps < self.cfg.max_steps:
            self.step(st, max_inner=self.cfg.max_steps - st.steps)
            if progress_every and st.steps % progress_every == 0:
                print(f"[{self.comp.name}] step={st.steps} "
                      f"occ={st.pool_occupancy} vpq={len(st.vpq)} "
                      f"thr={st.threshold} cand={st.candidates}")
            if mgr is not None and every > 0 and \
                    st.steps - last_ckpt >= every:
                self.save_checkpoint(mgr, st)
                last_ckpt = st.steps
        if mgr is not None and every > 0 and st.steps > last_ckpt:
            self.save_checkpoint(mgr, st)   # final state is restorable too
        if mgr is not None:
            mgr.wait()
        return self.finalize(st)


def make_sharded_bound_sync(axis_name: str, k: int):
    """The distributed engine's only collective: exchange per-shard result
    sets and return the *global* k-th best result key as the shared
    pruning threshold.

    Gathers each shard's k (state, key) pairs and dedups identical states
    (:func:`merge_topk`) before taking the k-th best: a deferred parent
    whose key already entered one shard's local result set can be
    rebalanced to another shard and deposit its key there too, and keys
    alone cannot distinguish that duplicate from a legitimate tie —
    double-counting it would over-tighten the threshold and prune true
    results (unsound).  All-gathering ``k * (S + 1)`` int32 per shard is
    still a few KB — pruning tightness costs near-zero bandwidth.

    Used inside ``shard_map`` when the frontier is sharded over the
    ``data`` axis (seed partitioning) — DESIGN.md §11.
    """
    def sync(local_result_states: jnp.ndarray,
             local_result_keys: jnp.ndarray) -> jnp.ndarray:
        alls = jax.lax.all_gather(local_result_states, axis_name)
        allk = jax.lax.all_gather(local_result_keys, axis_name)
        _, topk = merge_topk(alls.reshape(-1, alls.shape[-1]),
                             allk.reshape(-1), k)
        return jnp.where(topk[k - 1] > NEG, topk[k - 1], NEG)
    return sync


def make_stale_bound_sync(k: int):
    """The staleness-aware companion to :func:`make_sharded_bound_sync`
    (DESIGN.md §14): the threshold a shard prunes with *between* exchanges,
    computed with no collective at all.

    ``stale(last_exchanged, local_result_keys)`` returns
    ``max(last-exchanged global k-th best, fresh local k-th best)``.  Both
    operands are lower bounds on the current fresh global k-th best — the
    global result set only improves monotonically after the exchange, and
    any shard's local k-th best can only be dominated by the union's — so
    their max is too, which means interim pruning is at worst *looser*
    than per-step exchange and never drops a true result.  Folding the
    local k-th in (rather than the exchanged bound alone) keeps
    single-shard runs byte-identical for every ``sync_every`` and lets a
    shard that finds great results mid-segment prune aggressively without
    waiting for the next all-gather.
    """
    def stale(last_exchanged: jnp.ndarray,
              local_result_keys: jnp.ndarray) -> jnp.ndarray:
        kth = local_result_keys[k - 1]
        local = jnp.where(kth > NEG, kth, NEG)
        return jnp.maximum(last_exchanged, local)
    return stale
