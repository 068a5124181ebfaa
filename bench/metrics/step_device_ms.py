"""Device milliseconds per engine super-step: the seconds the device ran
the engine's step programs in the window (``jit_discovery_step``,
``jit_discovery_macro`` and their ``_sharded`` forms on the trace's
``XLA Modules`` line, averaged over devices) over the window's
super-steps (growth of ``engine_steps_total``)."""
import importlib

spans = importlib.import_module("bench.spans")

PROGRAMS = ("jit_discovery_step", "jit_discovery_macro",
            "jit_discovery_step_sharded", "jit_discovery_macro_sharded")


def read(ctx):
    steps = ctx["counters"].get("engine_steps_total", 0)
    w = spans.window(ctx)
    if w is None or steps <= 0:
        return None
    seconds = spans.program_seconds(*w)
    if not any(p in seconds for p in PROGRAMS):
        return None
    return 1e3 * sum(seconds.get(p, 0.0) for p in PROGRAMS) / steps
