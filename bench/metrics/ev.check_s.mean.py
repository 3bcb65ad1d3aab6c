"""Mean seconds of one EV call: the program's ``veer.ev.check`` spans that
start in the window (an answer from the verdict cache is no call).
``None`` where the program opens no such span."""

from bench import spans


def read(run):
    t = spans.of(run)
    if t is None:
        return None
    checks = spans.starting_in(t.spans, "veer.ev.check", t.window)
    return sum(s.dur_ns for s in checks) / len(checks) / 1e9 if checks else None
