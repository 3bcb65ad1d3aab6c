"""Share of the windows the search admitted for which it built sub-DAGs:
Σ``subdags`` over Σ``windows`` of the program's ``veer.search.decide``
spans that start in the window (a pair from the pair cache admits none).
The search reads a window through the version DAGs and builds its two
sub-DAGs only for a certificate or an EV that needs them.  ``None`` where
the decide spans carry no such counters, or admit no window."""

from bench import spans


def read(run):
    t = spans.of(run)
    if t is None:
        return None
    decide = [s.stats for s in spans.starting_in(t.spans, "veer.search.decide", t.window)
              if "windows" in s.stats and "subdags" in s.stats]
    windows = sum(s["windows"] for s in decide)
    return sum(s["subdags"] for s in decide) / windows if windows else None
