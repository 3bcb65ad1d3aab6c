"""Satisfiability problems decided per pair: the mean ``sat_calls`` of the
program's ``veer.search.decide`` spans that start in the window (a pair
answered from the pair cache decides none).  ``None`` where the program's
decide spans carry no ``sat_calls``."""

from bench import spans


def read(run):
    t = spans.of(run)
    if t is None:
        return None
    calls = [s.stats["sat_calls"] for s in spans.starting_in(t.spans, "veer.search.decide", t.window)
             if "sat_calls" in s.stats]
    return sum(calls) / len(calls) if calls else None
