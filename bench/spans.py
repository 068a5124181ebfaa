"""Reductions of a profiler trace's window to what the program's own
names say: the device seconds of each device program, and the host spans
of the program with the spans nested in them.

The program names its device programs (``jit_discovery_step``, ...) and
opens a ``jax.profiler.TraceAnnotation`` for each of its spans
(``engine.step``, ``engine.wait``, ...), so both appear in the trace under
names that do not change with the code's HLO.  The reductions are pure,
over :class:`devtrace.Event` rows with times in nanoseconds:

* :func:`program_seconds`: seconds per program on the ``XLA Modules``
  line of each device plane, the ``(hash)`` suffix stripped, clipped to
  the window, summed over devices and divided by their number;
* :func:`span_intervals`: each host span of one name that starts in the
  window, with the intervals of the spans of a second name nested in it
  on the same thread.

:func:`window` gives a per-layer reader the window's events: those its
``ctx`` carries, or else those of the trace the harness wrote for the
cell, loaded once per trace file.
"""
from __future__ import annotations

import bisect
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from . import devtrace

MODULES_LINE = "XLA Modules"
_HASH = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def program_name(name: str) -> str:
    """A device program's name without the ``(hash)`` the runtime adds:
    ``jit_sort(1381...)`` -> ``jit_sort``."""
    return _HASH.sub("", name.strip())


def program_seconds(events, t0: float, t1: float) -> Dict[str, float]:
    """Device seconds per program inside ``[t0, t1]`` ns, averaged over
    the devices that ran a program in the window."""
    per: Dict[str, float] = defaultdict(float)
    devices = set()
    for e in events:
        if not (e.plane.startswith("/device:") and e.line == MODULES_LINE):
            continue
        s, t = max(e.start_ns, t0), min(e.end_ns, t1)
        if t > s:
            per[program_name(e.name)] += (t - s) / 1e9
            devices.add(e.plane)
    return {k: v / len(devices) for k, v in per.items()}


def span_intervals(events, t0: float, t1: float, name: str,
                   child: Optional[str] = None
                   ) -> List[Tuple[Interval, List[Interval]]]:
    """Each host span ``name`` that starts inside ``[t0, t1)`` ns, in
    order of start, with the intervals of the ``child`` spans that lie
    inside it on the same thread."""
    parents = defaultdict(list)      # (plane, line) -> [(start, end)]
    children = defaultdict(list)
    for e in events:
        if not e.plane.startswith("/host:"):
            continue
        if e.name == name and t0 <= e.start_ns < t1:
            parents[e.plane, e.line].append((e.start_ns, e.end_ns))
        elif child is not None and e.name == child:
            children[e.plane, e.line].append((e.start_ns, e.end_ns))
    out = []
    for thread, spans in parents.items():
        spans.sort()
        starts = [s for s, _ in spans]
        nested: List[List[Interval]] = [[] for _ in spans]
        for s, t in children.get(thread, ()):
            # spans of one name on one thread do not overlap, so the
            # only parent that can hold a child is the last to start
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and t <= spans[i][1]:
                nested[i].append((s, t))
        out += [(p, sorted(c)) for p, c in zip(spans, nested)]
    return sorted(out)


_loaded: Dict[str, tuple] = {}


def window(ctx: dict):
    """``(events, t0, t1)`` of the traced window of the run ``ctx``
    describes, or None where the run has no trace."""
    if "events" in ctx:
        return ctx["events"], ctx["t0"], ctx["t1"]
    if ctx.get("trace") is None:
        return None
    from . import harness
    trace_dir = os.path.join(harness.SCRATCH, "trace", ctx["cell"]["name"])
    try:
        path = devtrace.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    key = f"{path}:{os.path.getmtime(path)}"
    if key not in _loaded:
        events = devtrace.load_events(path)
        _loaded.clear()
        _loaded[key] = (events,) + devtrace.window_of(events,
                                                      harness.WINDOW_SPAN)
    return _loaded[key]
