"""Mean seconds a device join probe call spends outside its own program:
dispatch, the wait behind other threads' programs and the copies back
(the program's ``veer.plane.join.probe`` spans less the device time of
``jit__join_probe_body``; see ``bench/spans.py``)."""

from bench import spans


def read(run):
    t = spans.of(run)
    return None if t is None else spans.probe_wait_s_mean(t)
