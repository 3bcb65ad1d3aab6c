"""Versions answered inside the window, per second of the window."""


def read(run):
    n = len(run.window_answers)
    return n / run.seconds if n else None
