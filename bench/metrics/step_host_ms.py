"""Host milliseconds of one engine step: the median, over the window's
``engine.step`` spans in the profiler trace, of the span's length less
that of its ``engine.wait`` child (where the host blocks on the device).
What is left is dispatch, the overflow fetch and push into the VPQ, and
refill: the host's share of a step's round trip."""
import importlib
import statistics

spans = importlib.import_module("bench.spans")


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    steps = spans.span_intervals(*w, "engine.step", child="engine.wait")
    if not steps:
        return None
    host = [(t - s) - sum(b - a for a, b in waits)
            for (s, t), waits in steps]
    return statistics.median(host) / 1e6
