"""Host-device round trips per executed query: ``stats.host_syncs`` of
the window's queries that the engine ran (not served from the cache),
summed and divided by their number."""


def read(ctx):
    ran = [r["stats"]["host_syncs"] for r in ctx["records"]
           if r["status"] == "ok" and not r["cached"] and
           "host_syncs" in r["stats"]]
    if not ran:
        return None
    return sum(ran) / len(ran)
