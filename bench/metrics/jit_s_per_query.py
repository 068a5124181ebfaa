"""Seconds of JAX tracing, lowering and backend compiling per executed
query: the window's growth of ``jax_trace_seconds_total``,
``jax_lower_seconds_total`` and ``jax_backend_compile_seconds_total``
(an observed ``DiscoveryService`` counts the process's JAX compile
events; a program loaded from the persistent cache counts under backend
compiling) over the queries the engine ran."""

COUNTERS = ("jax_trace_seconds_total", "jax_lower_seconds_total",
            "jax_backend_compile_seconds_total")


def read(ctx):
    ran = [r for r in ctx["records"] if r["status"] == "ok" and
           not r["cached"]]
    c = ctx["counters"]
    if not ran or any(name not in c for name in COUNTERS):
        return None
    return sum(c[name] for name in COUNTERS) / len(ran)
