"""Median seconds a version spends in the service outside the session's
own timed work: the benchmark's submit latency minus ``PairReport.wall_time``
(queueing behind other clients and the per-client ticket gate), over every
version submitted in the window."""

import numpy as np


def read(run):
    waits = [a.latency_s - a.wall_time_s for a in run.submitted]
    return float(np.percentile(waits, 50)) if waits else None
