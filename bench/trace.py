"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` flattens an ``.xplane.pb`` into ``Event`` tuples.  The reductions
take the plane and line that hold the device's operations as arguments:
on a TPU these are the ``/device:TPU:<n>`` planes, whose ``XLA Ops`` line
holds each operation and ``XLA Modules`` line each jitted program.

* ``busy_s``: the union of operation intervals inside the window, averaged
  over the device planes; ``1 - busy_s / window`` is the idle share.
* ``module_s``: summed device time of the programs whose name starts with
  a prefix (``jit__join_probe_body`` for the join probe).
* ``top_ops``: the operations that took most device time.
* ``idle_gaps``: the longest stretches with no device operation, each
  named by the host span (a ``TraceAnnotation``) that covers most of it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = "/device:TPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 120


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def select(events: Iterable[Event], plane: str, line: str) -> Dict[str, List[Event]]:
    """Events whose plane name starts with ``plane`` and line name with
    ``line``, by plane."""
    out: Dict[str, List[Event]] = defaultdict(list)
    for e in events:
        if e.plane.startswith(plane) and e.line.startswith(line):
            out[e.plane].append(e)
    return dict(out)


def merged(intervals: Iterable[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_s(events: Iterable[Event], window: Tuple[float, float],
           plane: str = DEVICE_PLANE, line: str = OPS_LINE) -> float:
    """Seconds with an operation running, averaged over the device planes."""
    per_plane = select(events, plane, line)
    if not per_plane:
        return 0.0
    lo, hi = window
    total = 0.0
    for evs in per_plane.values():
        total += sum(e - s for s, e in merged(((x.start_ns, x.start_ns + x.dur_ns) for x in evs), lo, hi))
    return total / len(per_plane) / 1e9


def module_s(events: Iterable[Event], prefix: str, window: Tuple[float, float],
             plane: str = DEVICE_PLANE, line: str = MODULES_LINE) -> Tuple[float, int]:
    """Summed device seconds and count of programs named ``prefix...``
    that start inside the window."""
    lo, hi = window
    secs, n = 0.0, 0
    for evs in select(events, plane, line).values():
        for e in evs:
            if e.name.startswith(prefix) and lo <= e.start_ns < hi:
                secs += e.dur_ns / 1e9
                n += 1
    return secs, n


def top_ops(events: Iterable[Event], window: Tuple[float, float], k: int = 10,
            plane: str = DEVICE_PLANE, line: str = OPS_LINE) -> List[list]:
    lo, hi = window
    tally: Dict[str, float] = defaultdict(float)
    per_plane = select(events, plane, line)
    for evs in per_plane.values():
        for e in evs:
            if lo <= e.start_ns < hi:
                tally[e.name] += e.dur_ns / 1e9
    ranked = sorted(tally.items(), key=lambda kv: -kv[1])[:k]
    # an HLO op's name carries its whole signature: keep its head
    return [[name[:NAME_CHARS], secs / max(1, len(per_plane))] for name, secs in ranked]


def idle_gaps(events: List[Event], window: Tuple[float, float], host_prefix: str,
              k: int = 10, plane: str = DEVICE_PLANE, line: str = OPS_LINE) -> List[list]:
    """The ``k`` longest device-idle stretches of the first device plane,
    each named by the host span starting with ``host_prefix`` that covers
    most of it (``"no span"`` where none does)."""
    lo, hi = window
    per_plane = select(events, plane, line)
    busy: List[Tuple[float, float]] = []
    if per_plane:
        first = per_plane[sorted(per_plane)[0]]
        busy = merged(((x.start_ns, x.start_ns + x.dur_ns) for x in first), lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    spans = [(e.start_ns, e.start_ns + e.dur_ns, e.name) for e in events
             if e.name.startswith(host_prefix)
             and not (e.plane.startswith(plane) and e.line.startswith(line))]
    out = []
    for gs, ge in gaps:
        cover: Dict[str, float] = defaultdict(float)
        for s, e, name in spans:
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0:
                cover[name] += overlap
        label = max(cover.items(), key=lambda kv: kv[1])[0] if cover else "no span"
        out.append([label, (ge - gs) / 1e9])
    return out


def span_window(events: Iterable[Event], name: str) -> Optional[Tuple[float, float]]:
    """``(start, end)`` in trace time of the host span called ``name``."""
    for e in events:
        if e.name == name:
            return e.start_ns, e.start_ns + e.dur_ns
    return None
