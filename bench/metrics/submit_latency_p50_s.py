"""Median seconds from ``submit`` to verdict plus served sinks, over every
version submitted in the window; a version still in flight at the close
counts with the latency it has when it is answered."""

import numpy as np


def read(run):
    lat = [a.latency_s for a in run.submitted]
    return float(np.percentile(lat, 50)) if lat else None
