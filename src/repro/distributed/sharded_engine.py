"""Sharded multi-device discovery engine (DESIGN.md §11).

Scales one query across all devices on the host while keeping the paper's
prioritized-expansion/pruning efficiency.  The decomposition follows
density-partitioned distributed subgraph mining (Aridhi et al.,
arXiv:1212.0017): partition-local search plus one small shared bound.

* **seed partitioning** — the initial frontier is dealt round-robin over
  ``shards`` devices (a 1-D ``data`` mesh); every later state stays on the
  shard that materialized its seed ancestor unless the rebalancer moves its
  spilled work.
* **one jitted shard_map super-step** — each shard runs the *identical*
  per-shard body, :meth:`repro.core.engine.Engine._step_impl` (dequeue →
  result merge → prune → targeted expansion → insert), so the single-device
  :class:`~repro.core.engine.Engine` is exactly the 1-shard specialization.
  The only collective inside the step is
  :func:`~repro.core.engine.make_sharded_bound_sync`: each shard's k
  result (state, key) pairs are gathered, identical states deduplicated,
  and the global k-th-best key becomes every shard's dominance threshold
  (k·(S+1) int32 per shard per step — pruning tightness at near-zero
  bandwidth, DESIGN.md §4).
* **per-shard spill** — each shard owns a host/disk
  :class:`~repro.core.vpq.VirtualPriorityQueue`; overflow blocks exit the
  jitted step per shard and refills apply late dominance pruning against
  the *global* threshold.
* **host-side rebalancing** — after refills, shards that cannot refill
  themselves (occupancy below the C/2 watermark, own VPQ empty) pull
  spilled work from the most-loaded VPQs.  The move is a priority-ordered
  k-way merge pop on the donor and a merge-sort insert on the recipient —
  the paper's priority order is preserved by merging, never shuffled.

Result parity is exact by construction: the result merge uses the
canonical total order of :func:`~repro.core.engine.merge_topk` (key
descending, state-words tie-break), and dominance pruning is sound, so any
complete run — single-device or any shard count — discovers every state
whose key reaches the final global threshold and selects the identical
top-k byte-for-byte (parity-asserted in ``tests/test_distributed_engine.py``
and ``benchmarks/bench_distributed.py``).

Host/device division follows the repo-wide rule (DESIGN.md §2): the jitted
shard_map owns every fixed-shape loop; the host only moves overflow /
refill / rebalance blocks and accumulates counters.

Macro-stepping (DESIGN.md §13) composes with sharding: under
``EngineConfig.steps_per_sync = T > 1`` the fused ``while_loop`` of
:meth:`repro.core.engine.Engine._macro_impl` runs *per shard inside one
shard_map*, and the per-shard continue/stop votes are reduced to one
global decision (``psum``) so every shard leaves the loop together and
the in-loop collectives stay aligned.  The loop returns to the host as
soon as *any* shard hits its refill watermark (with spill available
anywhere — the rebalancer can move it), fills its overflow accumulator,
or the fleet drains, so refill and rebalance cadence match the unfused
engine.

Staleness-tolerant bound exchange (DESIGN.md §14): under
``EngineConfig.sync_every = K > 1`` the §4 collective fires only every
K-th inner step; in between, each shard prunes against
``max(last-exchanged global bound, fresh local k-th best)``
(:func:`~repro.core.engine.make_stale_bound_sync`) — both lower bounds on
the fresh global k-th best, so interim pruning is at worst *looser* and
complete runs stay byte-identical for every K while collectives (the
all-gather *and* the exit votes) drop by a factor of K.
``EngineResult.syncs`` counts the exchanges actually run
(``ceil(inner_steps / K)``); ``host_syncs`` counts host round-trips.

Label-constrained computations (DESIGN.md §12) thread through unchanged:
the predicate's bitsets — class rows, allowed-vertex mask, restricted
adjacency — are entries of the computation's ``tables``, placed once on
every device of the mesh (replicated) and passed into the jitted
shard_map exactly like the adjacency itself, so the sharded engine needs
no label-specific code and the §11 byte-parity argument covers labeled
runs verbatim (asserted in ``tests/test_labeled.py``).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.api import NEG, SubgraphComputation
from repro.core.engine import (Engine, EngineConfig, EngineResult,
                               donatable_pool_argnums,
                               make_sharded_bound_sync,
                               make_stale_bound_sync, merge_topk,
                               named_program)
from repro.core.vpq import VirtualPriorityQueue


_STAT_KEYS = ("dequeued", "expanded", "created", "pruned",
              "pool_occupancy", "threshold")
_MACRO_STAT_KEYS = ("expanded", "created", "pruned", "pool_occupancy",
                    "threshold", "spill_count", "steps")


@dataclasses.dataclass
class ShardedEngineState:
    """Resumable sharded search state.

    Pool and result arrays are *global* views of the sharded layout:
    leading axis ``shards * per_shard_size``, sharded over the ``data``
    mesh axis by the jitted step.  VPQs and counters are host-side.
    """

    pool_states: jnp.ndarray      # [shards*C, S]
    pool_prio: jnp.ndarray        # [shards*C]
    pool_ub: jnp.ndarray          # [shards*C]
    result_states: jnp.ndarray    # [shards*k, S] (per-shard local top-k)
    result_keys: jnp.ndarray      # [shards*k]
    vpqs: List[VirtualPriorityQueue]
    pool_occupancy: np.ndarray    # [shards] int64
    tables: Any                   # the query's tables, replicated (§11)
    steps: int = 0
    candidates: int = 0
    expanded: int = 0
    pruned: int = 0
    refilled: int = 0
    rebalanced: int = 0
    syncs: int = 0                # §4 bound-exchange collectives run so far
    host_syncs: int = 0           # host↔device round-trips taken so far
    threshold: int = int(NEG)
    done: bool = False            # every shard pool and VPQ drained
    # per-macro-call bound traces (config.record_bound_trace): each entry
    # is a [shards, inner_steps] int32 pair — threshold actually used /
    # fresh per-step-exchange bound (DESIGN.md §14 invariant, test hook)
    bound_used: List[np.ndarray] = dataclasses.field(default_factory=list)
    bound_fresh: List[np.ndarray] = dataclasses.field(default_factory=list)


class ShardedEngine:
    """Runs one :class:`SubgraphComputation` sharded over a device mesh.

    Drop-in interface parity with :class:`~repro.core.engine.Engine`
    (``start`` / ``step`` / ``finalize`` / ``run``), so the service
    scheduler drives sharded queries unchanged.  ``config.batch`` /
    ``pool_capacity`` / ``max_children`` are per-shard shapes.
    """

    def __init__(self, comp: SubgraphComputation, config: EngineConfig):
        self.comp = comp
        self.cfg = config
        self.shards = config.shards
        if self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")
        devices = jax.devices()
        if self.shards > len(devices):
            raise ValueError(
                f"shards={self.shards} exceeds the {len(devices)} available "
                f"device(s); force host devices with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N "
                f"or lower `shards`")
        self.mesh = Mesh(np.asarray(devices[:self.shards]), ("data",))
        # host blocks go straight to their shards (never all to device 0);
        # graph tables are replicated onto every shard once per engine, and
        # a query's own tables (start) place only the leaves they replace
        self._data = NamedSharding(self.mesh, P("data"))
        self._replicated = NamedSharding(self.mesh, P())
        self.tables = jax.device_put(comp.tables, self._replicated)

        # staleness-tolerant bound exchange (DESIGN.md §14): K inner steps
        # per §4 all-gather.  K is clamped so one K-step segment's overflow
        # always fits an explicitly-sized accumulator, and steps_per_sync
        # is raised to a multiple of K so every fused macro call ends on an
        # exchange boundary — that makes the host-side collective count
        # exactly ceil(total_inner_steps / K) for complete runs.
        if config.sync_every < 1:
            raise ValueError(
                f"sync_every must be >= 1, got {config.sync_every}")
        blk = config.batch + max(config.max_children or 0, comp.num_actions)
        K = config.sync_every
        if config.overflow_accum:
            K = max(1, min(K, config.overflow_accum // blk))
        self.K = K
        T_eff = max(1, config.steps_per_sync)
        if K > 1:   # align fused calls to segment boundaries (forces T > 1)
            T_eff = -(-max(T_eff, K) // K) * K
        if config.record_bound_trace:
            T_eff = max(T_eff, 2)   # traces ride the fused macro path only

        # the per-shard engine: supplies the jit-free super-step body and
        # the derived per-shard shapes (B, C, M, S)
        self._eng = Engine(comp, dataclasses.replace(
            config, shards=1, steps_per_sync=T_eff, sync_every=1))
        self.B, self.C, self.M = self._eng.B, self._eng.C, self._eng.M
        self.S, self.k = self._eng.S, config.k

        # observability (DESIGN.md §16): share the inner engine's instance
        # (dataclasses.replace copied the observe/observability fields) so
        # sharded and per-shard telemetry land in one registry
        self.obs = self._eng.obs
        self._span = self.obs.tracer.span
        self._m_rebalanced = self.obs.counter(
            "engine_rebalanced_total",
            "spilled entries moved across shards")
        self._m_syncs = self.obs.counter(
            "engine_syncs_total", "bound-exchange collectives run")

        sync = make_sharded_bound_sync("data", self.k)
        spec = P("data")

        def body(pool_states, pool_prio, pool_ub, result_states, result_keys,
                 tables):
            (pool_states, pool_prio, pool_ub, result_states, result_keys,
             overflow, stats) = self._eng._step_impl(
                pool_states, pool_prio, pool_ub, result_states, result_keys,
                tables, bound_sync=sync)
            # scalar per-shard stats -> [1] so the mesh axis can concatenate
            stats = {name: stats[name].reshape(1) for name in _STAT_KEYS}
            return (pool_states, pool_prio, pool_ub, result_states,
                    result_keys, overflow, stats)

        self._step_sharded = jax.jit(jax.shard_map(
            named_program("discovery_step_sharded", body), mesh=self.mesh,
            in_specs=(spec,) * 5 + (P(),),
            out_specs=((spec,) * 5 + ((spec, spec, spec),
                                      {name: spec for name in _STAT_KEYS})),
            check_vma=False))
        # refill / rebalance blocks enter through the same merge-sort insert
        # as overflow handling, one fixed [shards*C] block per call
        self._insert_sharded = jax.jit(jax.shard_map(
            named_program("discovery_insert_sharded", self._eng._insert_impl),
            mesh=self.mesh, in_specs=(spec,) * 6, out_specs=(spec,) * 6,
            check_vma=False))

        # fused macro-step (DESIGN.md §13/§14): the per-shard while_loop
        # with the §4 threshold collective at segment heads (every step at
        # K == 1), the stale bound in between, and the per-shard
        # continue/stop votes psum-reduced at segment boundaries so all
        # shards exit together
        self.T = self._eng.T
        if self.T > 1:
            stale = make_stale_bound_sync(self.k)
            rec = bool(config.record_bound_trace)
            stat_keys = _MACRO_STAT_KEYS + (
                ("bound_used", "bound_fresh") if rec else ())

            def any_reduce(flag):
                return jax.lax.psum(flag.astype(jnp.int32), "data") > 0

            def macro_body(pool_states, pool_prio, pool_ub,
                           result_states, result_keys, tables, t_max,
                           vpq_flag, occ0):
                (ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, stats) = \
                    self._eng._macro_impl(
                        pool_states, pool_prio, pool_ub,
                        result_states, result_keys, tables, t_max,
                        vpq_flag[0], occ0[0],
                        bound_sync=sync, any_reduce=any_reduce,
                        sync_every=self.K, stale_sync=stale,
                        record_bounds=rec)
                # scalar per-shard stats -> [1]; [T] traces -> [1, T] so
                # the mesh axis concatenates them to [shards, T]
                stats = {name: stats[name].reshape((1, -1))
                         if name in ("bound_used", "bound_fresh")
                         else stats[name].reshape(1)
                         for name in stat_keys}
                return ps, pp, pu, rs, rk, acc_s, acc_p, acc_u, stats

            self._macro_sharded = jax.jit(jax.shard_map(
                named_program("discovery_macro_sharded", macro_body),
                mesh=self.mesh,
                in_specs=(spec,) * 5 + (P(), P(), spec, spec),
                out_specs=((spec,) * 8 +
                           ({name: spec for name in stat_keys},)),
                check_vma=False),
                donate_argnums=donatable_pool_argnums())

    # ----------------------------------------------------------------- start
    def _place(self, tables):
        """A query's ``tables`` replicated over the mesh (the engine's own
        when None); leaves already placed there are not copied."""
        if tables is None:
            return self.tables
        return jax.device_put(tables, self._replicated)

    def start(self, tables=None) -> ShardedEngineState:
        """Seed-partition the frontier and return a resumable state that
        searches with ``tables`` (default: the computation's)."""
        with self._span("engine.start"):
            return self._start_impl(self._place(tables))

    def _start_impl(self, tables) -> ShardedEngineState:
        cfg, S, C, k, shards = self.cfg, self.S, self.C, self.k, self.shards
        vpqs = []
        for i in range(shards):
            sub = (os.path.join(cfg.spill_dir, f"shard{i}")
                   if cfg.spill_dir is not None else None)
            vpqs.append(VirtualPriorityQueue(
                state_width=S, backend=cfg.spill, spill_dir=sub,
                obs=self.obs))

        states0, prio0, ub0 = (np.asarray(a) for a in
                               self._eng._init(tables))
        n0 = states0.shape[0]

        pool_states = np.zeros((shards, C, S), np.int32)
        pool_prio = np.full((shards, C), NEG, np.int32)
        pool_ub = np.full((shards, C), NEG, np.int32)
        occ = np.zeros(shards, np.int64)
        for i in range(shards):
            # round-robin seed partition: shard i gets seeds i, i+shards, ...
            s_i, p_i, u_i = states0[i::shards], prio0[i::shards], ub0[i::shards]
            order = np.argsort(p_i.astype(np.int64), kind="stable")[::-1]
            s_i, p_i, u_i = s_i[order], p_i[order], u_i[order]
            m = min(len(p_i), C)
            pool_states[i, :m], pool_prio[i, :m], pool_ub[i, :m] = \
                s_i[:m], p_i[:m], u_i[:m]
            occ[i] = m
            if len(p_i) > m:   # more seeds than per-shard pool slots
                vpqs[i].maybe_push(s_i[m:], p_i[m:], u_i[m:])

        pool_states, pool_prio, pool_ub, result_states, result_keys = \
            jax.device_put((pool_states.reshape(shards * C, S),
                            pool_prio.reshape(shards * C),
                            pool_ub.reshape(shards * C),
                            np.zeros((shards * k, S), np.int32),
                            np.full((shards * k,), NEG, np.int32)),
                           self._data)
        return ShardedEngineState(
            pool_states=pool_states, pool_prio=pool_prio, pool_ub=pool_ub,
            result_states=result_states, result_keys=result_keys,
            vpqs=vpqs, pool_occupancy=occ, tables=tables,
            candidates=int(n0))

    # ------------------------------------------------------------------ step
    def step(self, st: ShardedEngineState,
             max_inner: Optional[int] = None) -> ShardedEngineState:
        """Advance every shard one (macro-)step; spill, refill, rebalance.

        ``max_inner`` caps the fused super-step count exactly like
        :meth:`repro.core.engine.Engine.step` so step budgets truncate at
        the same count for any ``steps_per_sync``.
        """
        shards, cap = self.shards, self._eng.acc_cap
        if self.T == 1:
            with self._span("engine.step"):
                with self._span("engine.dispatch"):
                    (st.pool_states, st.pool_prio, st.pool_ub,
                     st.result_states, st.result_keys, overflow,
                     stats) = self._step_sharded(
                        st.pool_states, st.pool_prio, st.pool_ub,
                        st.result_states, st.result_keys, st.tables)
                with self._span("engine.wait"):
                    stats = jax.device_get(stats)  # each value: [shards]

                st.steps += 1
                st.syncs += 1          # one §4 exchange per unfused step
                st.host_syncs += 1
                st.expanded += int(stats["expanded"].sum())
                st.candidates += int(stats["created"].sum())
                st.pruned += int(stats["pruned"].sum())
                st.threshold = int(stats["threshold"][0])  # replicated, §4
                occ = stats["pool_occupancy"].astype(np.int64)

                with self._span("engine.fetch_overflow"):
                    o_s, o_p, o_u = (np.asarray(a) for a in overflow)
                    o_per = len(o_p) // shards
                    for i in range(shards):
                        sl = slice(i * o_per, (i + 1) * o_per)
                        st.vpqs[i].maybe_push(o_s[sl], o_p[sl], o_u[sl])
                st = self._refill_rebalance(st, occ)
            self._after_step(st, 1, 1, stats)
            return st

        t_cap = (self.T if max_inner is None
                 else max(1, min(self.T, int(max_inner))))
        with self._span("engine.step"):
            with self._span("engine.dispatch"):
                (st.pool_states, st.pool_prio, st.pool_ub,
                 st.result_states, st.result_keys, acc_s, acc_p, acc_u,
                 stats) = self._macro_sharded(
                    st.pool_states, st.pool_prio, st.pool_ub,
                    st.result_states, st.result_keys, st.tables,
                    np.int32(t_cap),
                    np.asarray([len(v) > 0 for v in st.vpqs]),
                    st.pool_occupancy.astype(np.int32))
            with self._span("engine.wait"):
                stats = jax.device_get(stats)     # each value: [shards]
            n = int(stats["steps"][0])            # uniform: global exit vote
            st.steps += n
            # every segment opens with one fresh exchange and runs <= K
            # steps, and fused calls end on segment boundaries (T is a
            # multiple of K), so this call ran exactly ceil(n / K)
            # collectives
            st.syncs += -(-n // self.K)
            st.host_syncs += 1
            if self.cfg.record_bound_trace:
                st.bound_used.append(np.asarray(stats["bound_used"])[:, :n])
                st.bound_fresh.append(
                    np.asarray(stats["bound_fresh"])[:, :n])
            st.expanded += int(stats["expanded"].sum())
            st.candidates += int(stats["created"].sum())
            st.pruned += int(stats["pruned"].sum())
            st.threshold = int(stats["threshold"][0])
            occ = stats["pool_occupancy"].astype(np.int64)
            spill = stats["spill_count"]
            if spill.any():   # ship each shard's valid accumulator prefix
                with self._span("engine.fetch_overflow"):
                    acc_s, acc_p, acc_u = (np.asarray(a)
                                           for a in (acc_s, acc_p, acc_u))
                    for i in range(shards):
                        w = int(spill[i])
                        if w:
                            base = i * cap
                            st.vpqs[i].maybe_push(acc_s[base:base + w],
                                                  acc_p[base:base + w],
                                                  acc_u[base:base + w])
            st = self._refill_rebalance(st, occ)
        self._after_step(st, n, -(-n // self.K), stats)
        return st

    def _after_step(self, st: ShardedEngineState, n_steps: int,
                    n_syncs: int, stats: dict) -> None:
        """Record one step() call's metrics (no-op handles when off)."""
        eng = self._eng
        eng._m_steps.inc(n_steps)
        eng._m_host_syncs.inc()
        self._m_syncs.inc(n_syncs)
        eng._m_expanded.inc(int(stats["expanded"].sum()))
        eng._m_candidates.inc(int(stats["created"].sum()))
        eng._m_pruned.inc(int(stats["pruned"].sum()))
        eng._g_occupancy.set(int(st.pool_occupancy.sum()))
        eng._g_threshold.set(st.threshold)

    # ----------------------------------------------------- refill/rebalance
    def _refill_rebalance(self, st: ShardedEngineState,
                          occ: np.ndarray) -> ShardedEngineState:
        shards, C, S = self.shards, self.C, self.S
        # ---- refill: per shard, below the C/2 watermark, from its own VPQ
        blk_s = np.zeros((shards, C, S), np.int32)
        blk_p = np.full((shards, C), NEG, np.int32)
        blk_u = np.full((shards, C), NEG, np.int32)
        fill = np.zeros(shards, np.int64)
        if any(occ[i] < C // 2 and len(st.vpqs[i]) for i in range(shards)):
            with self._span("engine.refill"):
                for i in range(shards):
                    if occ[i] < C // 2 and len(st.vpqs[i]):
                        r_s, r_p, r_u = st.vpqs[i].pop_chunk(
                            C - int(occ[i]), min_ub=st.threshold)
                        r = len(r_p)
                        if r:
                            blk_s[i, :r], blk_p[i, :r], blk_u[i, :r] = \
                                r_s, r_p, r_u
                            fill[i] = r
                            st.refilled += r
                            self._eng._m_refilled.inc(r)

        # ---- rebalance: shards that cannot refill themselves pull spilled
        # work from the most-loaded VPQs (priority order preserved: the
        # donor pop is a sorted k-way merge, the insert a merge-sort)
        needy = [i for i in range(shards)
                 if occ[i] + fill[i] < C // 2 and len(st.vpqs[i]) == 0]
        if needy:
            with self._span("engine.rebalance"):
                donors = sorted(
                    (i for i in range(shards) if len(st.vpqs[i])),
                    key=lambda i: -len(st.vpqs[i]))
                for i in needy:
                    for d in donors:
                        room = C // 2 - int(occ[i] + fill[i])
                        if room <= 0:
                            break
                        if not len(st.vpqs[d]):
                            continue
                        m_s, m_p, m_u = st.vpqs[d].pop_chunk(
                            min(room, len(st.vpqs[d])), min_ub=st.threshold)
                        m = len(m_p)
                        if m:
                            off = int(fill[i])
                            blk_s[i, off:off + m] = m_s
                            blk_p[i, off:off + m] = m_p
                            blk_u[i, off:off + m] = m_u
                            fill[i] += m
                            st.rebalanced += m
                            self._m_rebalanced.inc(m)

        if fill.any():
            (st.pool_states, st.pool_prio, st.pool_ub, ov_s, ov_p, ov_u) = \
                self._insert_sharded(
                    st.pool_states, st.pool_prio, st.pool_ub,
                    *jax.device_put((blk_s.reshape(shards * C, S),
                                     blk_p.reshape(shards * C),
                                     blk_u.reshape(shards * C)),
                                    self._data))
            # occ + fill <= C by construction, so the insert overflow is
            # all-NEG padding; push defensively anyway
            ov_s, ov_p, ov_u = (np.asarray(a) for a in (ov_s, ov_p, ov_u))
            per = len(ov_p) // shards
            for i in range(shards):
                sl = slice(i * per, (i + 1) * per)
                st.vpqs[i].maybe_push(ov_s[sl], ov_p[sl], ov_u[sl])

        st.pool_occupancy = occ + fill
        st.done = bool((st.pool_occupancy == 0).all()
                       and all(len(v) == 0 for v in st.vpqs))
        return st

    # -------------------------------------------------------------- finalize
    def finalize(self, st: ShardedEngineState) -> EngineResult:
        """Merge per-shard result sets canonically, close VPQs, package."""
        with self._span("engine.finalize"):
            return self._finalize_impl(st)

    def _finalize_impl(self, st: ShardedEngineState) -> EngineResult:
        result_states, result_keys = merge_topk(
            st.result_states, st.result_keys, self.k)
        per_shard = dict(
            spilled=[int(v.total_spilled) for v in st.vpqs],
            late_pruned=[int(v.total_late_pruned) for v in st.vpqs],
            vpq_backlog=[len(v) for v in st.vpqs],
            pool_occupancy=[int(x) for x in st.pool_occupancy])
        if self.cfg.record_bound_trace:
            # [shards, total_inner_steps] traces as per-shard lists
            used = (np.concatenate(st.bound_used, axis=1) if st.bound_used
                    else np.zeros((self.shards, 0), np.int32))
            fresh = (np.concatenate(st.bound_fresh, axis=1)
                     if st.bound_fresh
                     else np.zeros((self.shards, 0), np.int32))
            per_shard["bound_used"] = [list(map(int, row)) for row in used]
            per_shard["bound_fresh"] = [list(map(int, row))
                                        for row in fresh]
        for v in st.vpqs:
            v.close()
        return EngineResult(
            result_states=np.asarray(result_states),
            result_keys=np.asarray(result_keys),
            steps=st.steps, candidates=st.candidates, expanded=st.expanded,
            pruned=st.pruned,
            spilled=sum(per_shard["spilled"]), refilled=st.refilled,
            rebalanced=st.rebalanced,
            late_pruned=sum(per_shard["late_pruned"]), syncs=st.syncs,
            host_syncs=st.host_syncs, per_shard=per_shard)

    # ------------------------------------------------------- checkpointing
    _CKPT_SCALARS = ("steps", "candidates", "expanded", "pruned", "refilled",
                     "rebalanced", "syncs", "host_syncs", "threshold", "done")

    def save_checkpoint(self, mgr, st: ShardedEngineState,
                        blocking: bool = False) -> None:
        """Persist a sharded state: one manifest covers every shard, with
        per-shard VPQ snapshots under ``vpq/shard{i}`` subdirs of the step
        directory (DESIGN.md §15).  ``record_bound_trace`` journals are a
        test hook and are not checkpointed."""
        scalars = {name: getattr(st, name) for name in self._CKPT_SCALARS}
        scalars["pool_occupancy"] = [int(x) for x in st.pool_occupancy]

        def capture(tmp_dir: str) -> dict:
            vpqs = [v.snapshot(os.path.join(tmp_dir, "vpq", f"shard{i}"))
                    for i, v in enumerate(st.vpqs)]
            return {"kind": "sharded_engine", "shards": self.shards,
                    "scalars": scalars, "vpqs": vpqs}

        tree = dict(pool_states=st.pool_states, pool_prio=st.pool_prio,
                    pool_ub=st.pool_ub, result_states=st.result_states,
                    result_keys=st.result_keys)
        mgr.save(st.steps, tree, blocking=blocking, capture=capture)

    def resume(self, source, step: Optional[int] = None,
               tables=None) -> ShardedEngineState:
        """Rebuild a :class:`ShardedEngineState` whose continued run is
        byte-identical to an uninterrupted one, given the query's
        ``tables`` as in :meth:`repro.core.engine.Engine.resume`.  The
        checkpoint must have been written at the same shard count."""
        from repro.checkpoint.manager import CheckpointManager
        mgr = (source if isinstance(source, CheckpointManager)
               else CheckpointManager(source, obs=self.obs))
        manifest = mgr.read_manifest(step)
        step = manifest["step"]
        extra = manifest["extra"]
        if extra is None or extra.get("kind") != "sharded_engine":
            raise ValueError(
                f"step {step} in {mgr.dir} is not a sharded-engine "
                f"checkpoint")
        if extra["shards"] != self.shards:
            raise ValueError(
                f"checkpoint written at shards={extra['shards']}, engine "
                f"configured with shards={self.shards}")
        like = {name: np.zeros(
            [int(s) for s in leaf["shape"]], np.dtype(leaf["dtype"]))
            for leaf in manifest["leaves"]
            for name in [leaf["name"]]}
        tree = mgr.restore(like, step=step)
        vpqs = []
        for i, vman in enumerate(extra["vpqs"]):
            sub = (os.path.join(self.cfg.spill_dir, f"shard{i}")
                   if self.cfg.spill_dir is not None else None)
            vpqs.append(VirtualPriorityQueue.restore(
                vman, os.path.join(mgr.path(step), "vpq", f"shard{i}"),
                spill_dir=sub, obs=self.obs))
        scalars = dict(extra["scalars"])
        occ = np.asarray(scalars.pop("pool_occupancy"), np.int64)
        arrays = jax.device_put(
            {name: tree[name] for name in ("pool_states", "pool_prio",
                                           "pool_ub", "result_states",
                                           "result_keys")}, self._data)
        return ShardedEngineState(vpqs=vpqs, pool_occupancy=occ,
                                  tables=self._place(tables),
                                  **arrays, **scalars)

    # ------------------------------------------------------------------- run
    def run(self, progress_every: int = 0,
            resume: bool = False) -> EngineResult:
        """Run to completion, with the same periodic-checkpoint / resume
        contract as :meth:`repro.core.engine.Engine.run`."""
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
                print(f"[{self.comp.name}/x{self.shards}] step={st.steps} "
                      f"occ={st.pool_occupancy.tolist()} "
                      f"vpq={[len(v) for v in st.vpqs]} "
                      f"thr={st.threshold} cand={st.candidates}")
            if mgr is not None and every > 0 and \
                    st.steps - last_ckpt >= every:
                self.save_checkpoint(mgr, st)
                last_ckpt = st.steps
        if mgr is not None and every > 0 and st.steps > last_ckpt:
            self.save_checkpoint(mgr, st)
        if mgr is not None:
            mgr.wait()
        return self.finalize(st)
