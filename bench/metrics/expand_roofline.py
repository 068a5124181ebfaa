"""The expansion step's share of its roofline, in percent.

Each super-step of the engine has to read at least the extension table,
the vertex weights and the dequeued states (``roofline.expand_step_bytes``,
bound by HBM bandwidth, whatever kernel or jnp code implements it).  The
share is the window's super-steps (growth of ``engine_steps_total``) times
that least time, over the device's busy time in the window.  The bound
counts bytes only (the chip publishes no integer vector peak to rate the
step's B·N·N lanes of work against), so it stays far below 100% while a
step does that work.  Defined for
weighted clique, whose states are ``2W + 2`` words over ``W`` words of
bitset; other workloads read nothing.
"""
import importlib

roofline = importlib.import_module("bench.roofline")


def read(ctx):
    t = ctx["trace"]
    steps = ctx["counters"].get("engine_steps_total", 0)
    if (ctx["cell"]["traffic"]["family"] != "weighted-clique" or t is None
            or t["busy_s"] <= 0 or steps <= 0 or ctx["peaks"] is None):
        return None
    n = int(ctx["config"]["n"])
    w = (n + 31) // 32
    # B: the super-step batch the configuration states (``batch``), or
    # the one its requests set
    b = int(ctx["config"].get("request", {}).get("batch",
                                                 ctx["config"]["batch"]))
    least = roofline.least_seconds(
        roofline.expand_step_bytes(n, w, b, 2 * w + 2), ctx["peaks"])
    # a sharded super-step runs on every shard, and busy time is the
    # mean over devices, so the ratio is per device either way
    return 100.0 * steps * least / t["busy_s"]
