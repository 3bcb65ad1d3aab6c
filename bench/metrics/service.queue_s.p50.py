"""Median seconds a job waits between entering the service's queue and a
worker taking it (the ``queued_s`` of the program's
``veer.service.dequeue`` spans that start in the window)."""

from bench import spans


def read(run):
    t = spans.of(run)
    return None if t is None else spans.queue_s_p50(t)
