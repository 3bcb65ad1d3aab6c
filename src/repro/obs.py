"""Host spans of Veer's request path, on the JAX profiler's clock.

``span(name, **attrs)`` marks one stretch of host work as a
``jax.profiler.TraceAnnotation``: while a profiler trace records
(``jax.profiler.start_trace`` ... ``stop_trace``), the span lands in the
same ``.xplane.pb`` as the device's operations, with ``attrs`` as its
stats; with no trace recording it costs about a microsecond and records
nothing.  The trace is the only exporter: there is no option to set.

Attributes known only when the work is done are added on the open span:
``with span(...) as s: ...; s.set_metadata(verdict="eq")``.

``request(req_id)`` tags the calling thread: every span opened inside it
carries ``req=req_id``, so all spans of one job share an identifier and
one request can be followed across the job's spans.

Rule for the names (every one starts with ``veer.``): on one thread at
most one ``veer.`` span is open at a time, except two kinds of phases
inside their operation: the join phases (``veer.plane.join.*``) inside
``veer.exec.Join``, and the EV calls (``veer.ev.check``) inside
``veer.search.decide``.  A gap of the device is then named by the span
that covers it, and a job's context travels as ``req``, never as an
enclosing span.

jax is never imported here: a trace can only be recording in a process
that has already loaded ``jax.profiler``, so where it is absent a span is
a no-op.
"""

from __future__ import annotations

import contextlib
import sys
import threading
from typing import Iterator, Optional

_local = threading.local()


class _Off:
    """The span where no trace can be recording."""

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **attrs) -> None:
        return None


_OFF = _Off()


def span(name: str, **attrs):
    """A context manager that records ``name`` with ``attrs`` (and the
    thread's request id as ``req``) while a JAX profiler trace runs."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _OFF
    req = getattr(_local, "req", None)
    if req is not None:
        attrs["req"] = req
    return profiler.TraceAnnotation(name, **attrs)


@contextlib.contextmanager
def request(req_id: Optional[str]) -> Iterator[None]:
    """Tag the spans this thread opens inside the block with ``req_id``
    (``None``: with no request id)."""
    outer = getattr(_local, "req", None)
    _local.req = req_id
    try:
        yield
    finally:
        _local.req = outer
