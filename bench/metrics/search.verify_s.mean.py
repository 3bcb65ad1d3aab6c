"""Mean seconds of Algorithm 2 and the EVs per pair (``VeerStats.total_time``)."""


def read(run):
    vals = [a.verify_s for a in run.window_answers if a.is_pair]
    return sum(vals) / len(vals) if vals else None
