"""The trace reduction, on a small trace recorded on the CPU and on
intervals whose answers are known."""

import pathlib

import pytest

from bench import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data" / "cpu_probe.xplane.pb"
CPU = "/host:CPU"


@pytest.fixture(scope="module")
def events():
    return tr.load(str(DATA))


def test_loads_the_recorded_trace(events):
    window = tr.span_window(events, "bench.window")
    assert window is not None and window[1] > window[0]
    assert sum(e.name == "veer.exec.Join" for e in events) == 3


def test_busy_time_and_programs_on_the_recorded_trace(events):
    window = tr.span_window(events, "bench.window")
    length = (window[1] - window[0]) / 1e9
    busy = tr.busy_s(events, window, plane=CPU, line="tf_XLA")
    assert 0 < busy < length
    secs, n = tr.module_s(events, "PjRtCpuExecutable::ExecuteHelper", window,
                          plane=CPU, line="python")
    assert n == 3 and 0 < secs < length
    ops = tr.top_ops(events, window, plane=CPU, line="tf_XLA")
    assert 0 < len(ops) <= 10 and ops[0][1] >= ops[-1][1]
    gaps = tr.idle_gaps(events, window, "veer.", plane=CPU, line="tf_XLA")
    assert gaps and all(g[1] > 0 for g in gaps)
    assert {g[0] for g in gaps} <= {"veer.exec.Join", "veer.exec.Filter", "no span"}
    assert "veer.exec.Filter" in {g[0] for g in gaps}  # the sleeps ran under Filter spans


def _ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, float(start), float(dur))


def test_union_idle_gaps_and_average_over_chips():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    evs = [
        _ev(d0, "XLA Ops", "a", 100, 100), _ev(d0, "XLA Ops", "b", 150, 100),
        _ev(d0, "XLA Ops", "c", 600, 200), _ev(d1, "XLA Ops", "a", 0, 50),
        _ev(d0, "XLA Modules", "jit__join_probe_body(1)", 100, 150),
        _ev(d0, "XLA Modules", "jit_other", 600, 200),
        _ev("/host:CPU", "thread", "veer.exec.Join", 250, 300),
        _ev("/host:CPU", "thread", "veer.exec.Filter", 800, 200),
    ]
    window = (0.0, 1000.0)
    # device 0: [100, 250) and [600, 800) = 350 ns; device 1: [0, 50) = 50 ns
    assert tr.busy_s(evs, window) == pytest.approx(200e-9)
    assert tr.module_s(evs, "jit__join_probe_body", window) == (pytest.approx(150e-9), 1)
    gaps = tr.idle_gaps(evs, window, "veer.")
    assert gaps == [["veer.exec.Join", pytest.approx(350e-9)],
                    ["veer.exec.Filter", pytest.approx(200e-9)],
                    ["no span", pytest.approx(100e-9)]]
    assert tr.merged([(5, 1), (0, 3), (2, 4)], 0, 10) == [(0, 4)]
