#!/usr/bin/env python3
"""Drive the served discovery path once on a TPU and check its answers.

    python chip_smoke.py                  # one chip, N = 32768 graphs
    python chip_smoke.py --chips 4        # four chips: sharded clique only
    python chip_smoke.py --cpu-rehearsal  # tiny graphs on the CPU

Every request goes through ``DiscoveryService.serve``, the call the JSONL
server (``launch/serve.py``) makes, on graphs generated from ``--seed``.
One chip runs, at N = 32768 vertices (W = 1024 bitset words, clique state
width S = 2050; the dense layout's ceiling is N ≈ 46k):

* clique k=3 on a planted 12-clique, with the Pallas kernel, with the jnp
  reference, and fused (``steps_per_sync=16``, the donated macro path);
* iso k=3 on an 8-label graph, with and without the kernel, and on a small
  labelled graph against the brute-force oracle;
* the compiled clique step, which must hold the kernel (``tpu_custom_call``).

``--chips 4`` runs the clique request at ``shards=4`` with ``sync_every``
1 and 4 against ``shards=1``, and checks that the sharded pool is spread
over the four devices.

Each phase prints one JSON line: wall seconds (the response is on the
host, so the device work has finished), backend compile seconds within
it, and ``peak_bytes_in_use``.  The last line is
``{"ok": true, "device": {...}}``.  Any failed check exits non-zero; so
does a run that finds no TPU, and the CPU rehearsal never reports ok.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
N_FULL = 32768          # W = 1024 words; S = 2W + 2 = 2050 for clique
N_REHEARSAL = 512
ISO_QUERY = dict(q_edges=((0, 1), (1, 2)), q_labels=(0, 1, 2))


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(**fields) -> None:
    print(json.dumps(fields, sort_keys=True), flush=True)


class Smoke:
    """Runs phases against one DiscoveryService and records their cost."""

    def __init__(self, jax, svc):
        self.jax = jax
        self.svc = svc
        self.compile_s = 0.0
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        def on_duration(event, duration, **_kw):
            if event == BACKEND_COMPILE_EVENT:
                self.compile_s += duration
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def peak_bytes(self):
        stats = self.jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def serve(self, name: str, **req) -> dict:
        from repro.service import DiscoveryRequest
        c0, t0 = self.compile_s, time.perf_counter()
        resp = self.svc.serve([DiscoveryRequest(use_cache=False, **req)])[0]
        wall = time.perf_counter() - t0
        check(resp.status == "ok", f"{name}: {resp.error}")
        emit(phase=name, wall_s=wall, compile_s=self.compile_s - c0,
             peak_bytes_in_use=self.peak_bytes(),
             result_keys=resp.result_keys, terminated=resp.terminated,
             steps=resp.stats["steps"], candidates=resp.stats["candidates"],
             host_syncs=resp.stats["host_syncs"])
        check(resp.terminated == "complete",
              f"{name}: run ended by {resp.terminated}")
        return resp


def answer(resp) -> str:
    """The bytes that must match across execution paths."""
    return json.dumps([resp.result_keys, resp.results])


def one_chip(smoke: Smoke, kernel_expected: bool) -> None:
    import jax
    import numpy as np
    from repro.core.engine import Engine
    from repro.core.exhaustive import brute_force_iso
    from repro.service.api import DiscoveryRequest, compile_request

    clique = dict(graph="clique", workload="clique", k=3)
    kern = smoke.serve("clique_kernel", use_pallas=True, **clique)
    check(kern.result_keys == [12, 11, 11],
          f"clique top-3 keys {kern.result_keys}, want [12, 11, 11]")
    ref = smoke.serve("clique_jnp", use_pallas=False, **clique)
    check(answer(ref) == answer(kern) and
          ref.stats["candidates"] == kern.stats["candidates"],
          "clique: kernel and jnp paths disagree")
    fused = smoke.serve("clique_kernel_T16", use_pallas=True,
                        steps_per_sync=16, **clique)
    check(answer(fused) == answer(kern) and
          fused.stats["candidates"] == kern.stats["candidates"] and
          fused.stats["steps"] == kern.stats["steps"],
          "clique: steps_per_sync=16 disagrees with steps_per_sync=1")

    iso = dict(graph="labeled", workload="iso", k=3, **ISO_QUERY)
    iso_k = smoke.serve("iso_kernel", use_pallas=True, **iso)
    check(len(iso_k.result_keys) == 3, "iso: fewer than 3 results")
    iso_r = smoke.serve("iso_jnp", use_pallas=False, **iso)
    check(answer(iso_r) == answer(iso_k) and
          iso_r.stats["candidates"] == iso_k.stats["candidates"],
          "iso: kernel and jnp paths disagree")
    small = smoke.svc.registry.get("labeled-small")
    oracle = [s for s, _ in brute_force_iso(
        small, list(ISO_QUERY["q_edges"]), list(ISO_QUERY["q_labels"]),
        induced=True, k=3)]
    iso_s = smoke.serve("iso_small_kernel", graph="labeled-small",
                        workload="iso", k=3, use_pallas=True, **ISO_QUERY)
    check(iso_s.result_keys == oracle,
          f"iso: keys {iso_s.result_keys} != brute force {oracle}")

    # the kernel-path step the clique phases ran, compiled for this device
    cq = compile_request(DiscoveryRequest(use_pallas=True, **clique),
                         smoke.svc.registry)
    eng = Engine(cq.comp, cq.engine_cfg)
    shape = [(eng.C, eng.S), (eng.C,), (eng.C,), (eng.k, eng.S), (eng.k,)]
    t0 = time.perf_counter()
    lowered = eng._step.lower(
        *[jax.ShapeDtypeStruct(s, np.int32) for s in shape], cq.comp.tables)
    compiled = lowered.compile()
    text = compiled.as_text() or lowered.as_text()
    has_kernel = "tpu_custom_call" in text
    emit(phase="kernel_in_step", compile_s=time.perf_counter() - t0,
         tpu_custom_call=has_kernel)
    check(has_kernel == kernel_expected,
          f"compiled step holds the kernel: {has_kernel}, expected "
          f"{kernel_expected}")


def four_chips(smoke: Smoke) -> None:
    from repro.distributed import ShardedEngine
    from repro.service.api import DiscoveryRequest, compile_request

    clique = dict(graph="clique", workload="clique", k=3, use_pallas=True)
    base = smoke.serve("clique_shards1", **clique)
    check(base.result_keys == [12, 11, 11],
          f"clique top-3 keys {base.result_keys}, want [12, 11, 11]")
    for k_sync in (1, 4):
        got = smoke.serve(f"clique_shards4_sync{k_sync}", shards=4,
                          sync_every=k_sync, **clique)
        check(answer(got) == answer(base),
              f"shards=4 sync_every={k_sync} disagrees with shards=1")

    cq = compile_request(DiscoveryRequest(shards=4, **clique),
                         smoke.svc.registry)
    eng = ShardedEngine(cq.comp, cq.engine_cfg)
    st = eng.start()
    placed = []
    for when in ("start", "step"):
        if when == "step":
            eng.step(st)
        shards = st.pool_states.addressable_shards
        devices = sorted(s.device.id for s in shards)
        rows = sorted({s.data.shape[0] for s in shards})
        placed.append(dict(when=when, devices=devices, rows=rows))
        check(len(set(devices)) == 4 and rows == [eng.C],
              f"pool after {when} on devices {devices}, rows {rows}")
    eng.finalize(st)
    emit(phase="pool_placement", placement=placed)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help=f"run every phase at N={N_REHEARSAL} on the CPU "
                         f"(interpreted kernel); never reports ok")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.chips}").strip()

    # the TPU library logs under the system temp directory unless told
    # otherwise when it loads; keep its logs in the checkout
    log_dir = os.path.join(ROOT, ".tpu_logs")
    os.makedirs(log_dir, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", log_dir)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.data.synthetic_graphs import (labeled_graph,
                                             planted_clique_graph)
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.service import DiscoveryService, GraphRegistry

    devices = jax.devices()
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices))
    if not args.cpu_rehearsal and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {device}); a run "
              f"without the chip proves nothing", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    # the rehearsal compiles for the CPU, where nothing needs caching
    emit(phase="start", device=device,
         compile_cache=None if args.cpu_rehearsal else enable_compile_cache())

    n = N_REHEARSAL if args.cpu_rehearsal else N_FULL
    t0 = time.perf_counter()
    registry = GraphRegistry()
    registry.register("clique", planted_clique_graph(
        n=n, m=8 * n, clique_size=12, seed=args.seed))
    if args.chips == 1:
        registry.register("labeled", labeled_graph(n, 8 * n, 8,
                                                   seed=args.seed))
        registry.register("labeled-small", labeled_graph(
            120, 420, 3, seed=args.seed))
    emit(phase="graphs", n=n, seconds=time.perf_counter() - t0)

    smoke = Smoke(jax, DiscoveryService(registry=registry))
    try:
        if args.chips == 4:
            four_chips(smoke)
        else:
            one_chip(smoke, kernel_expected=not args.cpu_rehearsal)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    if args.cpu_rehearsal:
        emit(ok=False, rehearsal="cpu", device=device)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
