"""Megabytes (10**6 bytes) moved between the device pool and the host
spill per executed query: the window's growth of ``vpq_spill_bytes_total``
and ``vpq_refill_bytes_total`` over the queries the engine ran."""


def read(ctx):
    ran = [r for r in ctx["records"] if r["status"] == "ok" and
           not r["cached"]]
    c = ctx["counters"]
    if not ran or "vpq_spill_bytes_total" not in c:
        return None
    moved = c["vpq_spill_bytes_total"] + c.get("vpq_refill_bytes_total", 0)
    return moved / 1e6 / len(ran)
