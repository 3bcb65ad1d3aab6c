"""The program's own spans in a traced run, with their attributes.

The program opens ``veer.`` spans (``repro.obs``) where its work happens,
as ``jax.profiler.TraceAnnotation``s; their attributes are the events'
stats in the run's ``.xplane.pb``, on the clock of the device's
operations.  ``load`` reads them out with the thread each ran on, beside
the device events that ``bench.trace`` reduces.  The reductions:

* ``queue_s_p50``: median ``queued_s`` of the ``veer.service.dequeue``
  spans (a worker taking a job) that start in the window;
* ``probe_wait_s_mean``: per ``veer.plane.join.probe`` span starting in
  the window, the time outside its own device program: the spans' summed
  seconds less the device seconds of the ``jit__join_probe_body``
  programs that start between the first span's start and the last one's
  end, over the number of spans;
* ``join_host_s_mean``: mean self time of the ``veer.exec.Join`` spans
  that start in the window, less their ``veer.plane.join.probe`` child.

Each gives ``None`` where the program opened none of the spans it reads.

A reader sees only the run.  The harness writes a traced run's trace to
``<root>/.bench_trace``, and ``bench/run.py`` passes the checkout's root,
so ``of(run)`` reads it there, and only if its window has the length of
the run's (``run.trace["window_s"]``).
"""

from __future__ import annotations

import functools
import glob
import os
import pathlib
import statistics
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench import harness
from bench import trace as tr

TRACE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".bench_trace"
DEQUEUE = "veer.service.dequeue"
JOIN = "veer.exec.Join"
PROBE = "veer.plane.join.probe"
JOIN_PHASES = "veer.plane.join."
PROBE_PROGRAM = "jit__join_probe_body"


class Span(NamedTuple):
    thread: str            # the host plane and line: one thread
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Trace(NamedTuple):
    events: List[tr.Event]  # the device planes' events
    spans: List[Span]       # the host's veer. spans and the window
    window: Tuple[float, float]


def load(path: str) -> Tuple[List[tr.Event], List[Span]]:
    """The device events and the host spans named ``veer.`` (and the
    benchmark's window span) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    events: List[tr.Event] = []
    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(tr.DEVICE_PLANE)
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}#{i}:{line.name}"
            for ev in line.events:
                if device:
                    events.append(tr.Event(plane.name, line.name, ev.name,
                                           float(ev.start_ns), float(ev.duration_ns)))
                elif ev.name.startswith(harness.SPAN_PREFIX) or ev.name == harness.WINDOW_SPAN:
                    spans.append(Span(thread, ev.name, float(ev.start_ns),
                                      float(ev.duration_ns), dict(ev.stats)))
    return events, spans


@functools.lru_cache(maxsize=1)
def _load_file(path: str, mtime_ns: int) -> Tuple[List[tr.Event], List[Span]]:
    return load(path)


def of(run) -> Optional[Trace]:
    """The spans of ``run``'s trace, or ``None`` where the run was not
    traced or the trace on disk is not its own."""
    if run.trace is None:
        return None
    paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        return None
    events, spans = _load_file(paths[0], os.stat(paths[0]).st_mtime_ns)
    window = [(s.start_ns, s.end_ns) for s in spans if s.name == harness.WINDOW_SPAN]
    if len(window) != 1 or abs((window[0][1] - window[0][0]) / 1e9
                               - run.trace["window_s"]) > 1e-9:
        return None
    return Trace(events, spans, window[0])


def starting_in(spans: List[Span], name: str, window: Tuple[float, float]) -> List[Span]:
    lo, hi = window
    return [s for s in spans if s.name == name and lo <= s.start_ns < hi]


def outermost(spans: List[Span]) -> List[Span]:
    """``spans`` less those inside another of the same name on the same
    thread: the harness's own ``veer.exec.<Op>`` span around
    ``execute_op`` encloses the program's."""
    out: List[Span] = []
    for s in sorted(spans, key=lambda s: (s.thread, s.start_ns, -s.dur_ns)):
        if out and out[-1].thread == s.thread and s.end_ns <= out[-1].end_ns:
            continue
        out.append(s)
    return out


def queue_s_p50(t: Trace) -> Optional[float]:
    waits = [float(s.stats["queued_s"]) for s in starting_in(t.spans, DEQUEUE, t.window)]
    return statistics.median(waits) if waits else None


def probe_wait_s_mean(t: Trace) -> Optional[float]:
    probes = starting_in(t.spans, PROBE, t.window)
    if not probes or not tr.select(t.events, tr.DEVICE_PLANE, tr.MODULES_LINE):
        return None
    first = min(s.start_ns for s in probes)
    last = max(s.end_ns for s in probes)
    device_s, _ = tr.module_s(t.events, PROBE_PROGRAM, (first, last))
    return (sum(s.dur_ns for s in probes) / 1e9 - device_s) / len(probes)


def join_host_s_mean(t: Trace) -> Optional[float]:
    if not any(s.name.startswith(JOIN_PHASES) for s in t.spans):
        return None  # a program without join phase spans
    joins = outermost(starting_in(t.spans, JOIN, t.window))
    if not joins:
        return None
    probes: Dict[str, List[Span]] = {}
    for s in t.spans:
        if s.name == PROBE:
            probes.setdefault(s.thread, []).append(s)
    total = 0.0
    for j in joins:
        inner = sum(p.dur_ns for p in probes.get(j.thread, ())
                    if p.start_ns >= j.start_ns and p.end_ns <= j.end_ns)
        total += j.dur_ns - inner
    return total / len(joins) / 1e9
