"""Share of the versions' operators that ran (``ops_executed / ops_total``),
the rest reused from the store, derived by delta rules or skipped."""


def read(run):
    total = sum(a.ops_total for a in run.window_answers)
    return sum(a.ops_executed for a in run.window_answers) / total if total else None
