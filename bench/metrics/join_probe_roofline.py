"""The device join probe's share of its roofline, in percent.

The probe (``jit__join_probe_body``: two searchsorted passes of the left
keys into the sorted right keys) moves at least the int64 keys of both
sides in and two int64 window bounds per left row out:
``8 * (nl + nr) + 16 * nl`` bytes for ``nl`` left and ``nr`` right rows.
Rows are the join's logical rows, not the padded buckets, so the count
reads the same work whatever implements the probe.  It does no floating
point work, so HBM bandwidth bounds it: the least time is bytes over the
peak bandwidth, and the share is that over the summed device time of the
probe programs in the traced window.
"""


def probe_bytes(nl: int, nr: int) -> int:
    return 8 * (nl + nr) + 16 * nl


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    secs = run.trace["probe_s"]
    if not secs or not run.probe_calls:
        return None
    least = sum(probe_bytes(nl, nr) for nl, nr in run.probe_calls) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
