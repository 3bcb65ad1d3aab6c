"""Mean seconds of executing a version (``ExecStats.wall_time``: the
seeded, delta or full run, store reads and writes included)."""


def read(run):
    vals = [a.exec_s for a in run.window_answers]
    return sum(vals) / len(vals) if vals else None
