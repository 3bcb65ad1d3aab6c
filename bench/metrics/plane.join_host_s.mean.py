"""Mean host seconds of a join: the self time of the program's
``veer.exec.Join`` spans less their ``veer.plane.join.probe`` child (key
factorization, the argsort, window expansion and the column takes)."""

from bench import spans


def read(run):
    t = spans.of(run)
    return None if t is None else spans.join_host_s_mean(t)
