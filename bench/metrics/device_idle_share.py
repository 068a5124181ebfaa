"""Share of the window in which the device ran no operation, in percent,
from the profiler trace (mean over the cell's devices)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
