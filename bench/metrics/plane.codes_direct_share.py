"""Share of join key columns factorized without a sort: 1 less the summed
``sorted`` over the summed ``keys`` of the program's
``veer.plane.join.codes`` spans that start in the window (``sorted``
counts the key columns that took ``np.unique``).  ``None`` where the
program's codes spans carry no ``keys``."""

from bench import spans


def read(run):
    t = spans.of(run)
    if t is None:
        return None
    codes = [s.stats for s in spans.starting_in(t.spans, "veer.plane.join.codes", t.window)
             if "keys" in s.stats and "sorted" in s.stats]
    keys = sum(int(c["keys"]) for c in codes)
    return 1.0 - sum(int(c["sorted"]) for c in codes) / keys if keys else None
