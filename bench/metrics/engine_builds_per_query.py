"""Engines the service built per executed query: the window's growth of
``service_engine_builds_total`` (engine-cache misses, each a
``compile_request`` and a new ``Engine`` whose programs are traced and
lowered again) over the queries the engine ran."""


def read(ctx):
    ran = [r for r in ctx["records"] if r["status"] == "ok" and
           not r["cached"]]
    builds = ctx["counters"].get("service_engine_builds_total")
    if not ran or builds is None:
        return None
    return builds / len(ran)
