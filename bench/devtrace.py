"""Reduction of a profiler trace to device busy time, top device
operations and what the host was doing while the device sat idle.

The profiler writes an ``.xplane.pb``; :func:`load_events` flattens it to
:class:`Event` rows with absolute start and end times in nanoseconds.
Device operations are the events of the ``XLA Ops`` line of each
``/device:<kind>:<i>`` plane.  Host events are those of the ``/host:``
planes: the runtime's own trace points and the harness's
``jax.profiler.TraceAnnotation`` spans, on the same clock.

:func:`reduce_events` is pure and is what the tests check:

* busy time of a device: the union of its operations' intervals, clipped
  to the window; ``busy_s`` is the mean over devices;
* ``device_ops``: seconds per operation (its HLO name and result type,
  :func:`op_name`), clipped to the window,
  summed over devices and divided by their number, longest first;
* ``idle_gaps``: every instant of the window at which a device runs no
  operation is charged to the innermost (shortest) host event open at that
  instant, or to ``host idle`` where none is; seconds per name, averaged
  over devices, longest first.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
HOST_IDLE = "host idle"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not (plane.name.startswith("/device:")
                or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns),
                                 float(e.start_ns + e.duration_ns)))
    return out


def op_name(name: str) -> str:
    """An operation's HLO text cut to its name and result type, e.g.
    ``%fusion.3 = s32[64,4096]``."""
    return name.split("{", 1)[0].strip()


def is_device_op(e: Event) -> bool:
    return (e.plane.startswith("/device:") and
            not e.plane.startswith("/device:CPU") and e.line == OPS_LINE)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: List[Tuple[float, float]], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def attribute(spans: List[Tuple[float, float]], host: List[Event]
              ) -> Dict[str, float]:
    """Seconds of ``spans`` (sorted, disjoint) charged to the innermost
    open host event at each instant."""
    bounds = sorted({t for s, e in spans for t in (s, e)} |
                    {t for h in host for t in (h.start_ns, h.end_ns)})
    starts = sorted(host, key=lambda h: h.start_ns)
    out: Dict[str, float] = defaultdict(float)
    active: List[Tuple[float, int, Event]] = []     # (duration, id, event)
    i = j = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i].start_ns <= a:
            h = starts[i]
            heapq.heappush(active, (h.end_ns - h.start_ns, i, h))
            i += 1
        # an ended event shorter than every open one surfaces at the top
        while active and active[0][2].end_ns <= a:
            heapq.heappop(active)
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        if j < len(spans) and spans[j][0] <= a:
            name = active[0][2].name if active else HOST_IDLE
            out[name] += (b - a) / 1e9
    return out


def reduce_events(events: List[Event], t0: float, t1: float,
                  exclude_host: Tuple[str, ...] = ()) -> Optional[dict]:
    """Busy, top operations and idle attribution inside ``[t0, t1]`` ns;
    None where the trace holds no device operation in the window."""
    per_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    op_s: Dict[str, float] = defaultdict(float)
    host = []
    for e in events:
        s, t = max(e.start_ns, t0), min(e.end_ns, t1)
        if t <= s:
            continue
        if is_device_op(e):
            per_dev[e.plane].append((s, t))
            op_s[op_name(e.name)] += (t - s) / 1e9
        elif e.plane.startswith("/host:") and e.name not in exclude_host:
            host.append(Event(e.plane, e.line, e.name, s, t))
    if not per_dev:
        return None
    n_dev = len(per_dev)
    busy, idle = {}, defaultdict(float)
    for dev, iv in sorted(per_dev.items()):
        merged = union(iv)
        busy[dev] = sum(e - s for s, e in merged) / 1e9
        for name, sec in attribute(gaps(merged, t0, t1), host).items():
            idle[name] += sec / n_dev
    return dict(
        window_s=(t1 - t0) / 1e9,
        busy_s=sum(busy.values()) / n_dev,
        busy_by_device=busy,
        device_ops=sorted(([k, v / n_dev] for k, v in op_s.items()),
                          key=lambda r: -r[1]),
        idle_gaps=sorted(([k, v] for k, v in idle.items()),
                         key=lambda r: -r[1]))


def window_of(events: List[Event], name: str) -> Tuple[float, float]:
    """Start and end of the host span ``name`` (the measured window)."""
    spans = [e for e in events if e.name == name and
             e.plane.startswith("/host:")]
    if len(spans) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(spans)}")
    return spans[0].start_ns, spans[0].end_ns
