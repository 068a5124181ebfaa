"""Subgraphs materialized per executed query (``stats.candidates``, the
paper's own measure of pruning), over the window's queries that the
engine ran."""


def read(ctx):
    ran = [r["stats"]["candidates"] for r in ctx["records"]
           if r["status"] == "ok" and not r["cached"] and
           "candidates" in r["stats"]]
    if not ran:
        return None
    return sum(ran) / len(ran)
