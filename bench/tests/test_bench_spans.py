"""The reduction of the program's own spans (``bench/spans.py``), on spans
whose answers are known and on a tiny traced run on the CPU."""

import pytest

from bench import spans
from bench import trace as tr
from bench.tests import tiny

WINDOW = (0.0, 1000.0)


def _span(thread, name, start, dur, **stats):
    return spans.Span(thread, name, float(start), float(dur), stats)


def _probe_program(name, start, dur):
    return tr.Event("/device:TPU:0", "XLA Modules", name, float(start), float(dur))


def _trace():
    return spans.Trace(
        events=[_probe_program("jit__join_probe_body(7)", 160, 150),
                _probe_program("jit__join_probe_body(7)", 360, 100),
                _probe_program("jit__join_probe_body(7)", 900, 50),  # after the last probe span
                _probe_program("jit_other", 200, 50)],
        spans=[
            _span("w0", "bench.window", 0, 1000),
            _span("w0", "veer.service.dequeue", 10, 1, queued_s=0.5, req="a:0"),
            _span("w1", "veer.service.dequeue", 20, 1, queued_s=3.0, req="b:0"),
            _span("w2", "veer.service.dequeue", 30, 1, queued_s=1.5, req="c:0"),
            _span("w0", "veer.service.dequeue", 1200, 1, queued_s=100.0, req="a:1"),
            # the harness's span around execute_op, the program's inside it
            _span("w0", "veer.exec.Join", 100, 300),
            _span("w0", "veer.exec.Join", 101, 298, op="j", rows_in=9),
            _span("w0", "veer.plane.join.codes", 102, 38, nl=5, nr=4, device=1),
            _span("w0", "veer.plane.join.probe", 150, 200, nl=5, nr=4),
            # a dense join: no probe, all host
            _span("w1", "veer.exec.Join", 500, 100),
            _span("w1", "veer.exec.Join", 1100, 100),  # starts after the window
            # another thread's probe, longer than its device program
            _span("w2", "veer.exec.Join", 190, 320),
            _span("w2", "veer.plane.join.probe", 200, 300, nl=5, nr=4),
        ],
        window=WINDOW)


def test_queue_wait_is_the_median_of_the_pickups_in_the_window():
    assert spans.queue_s_p50(_trace()) == 1.5


def test_probe_wait_is_the_probe_spans_less_their_device_programs():
    # spans 200 + 300 ns; programs starting in [150, 500): 150 + 100 ns
    assert spans.probe_wait_s_mean(_trace()) == pytest.approx((500 - 250) / 2 * 1e-9)


def test_join_host_time_is_the_self_time_without_the_probe():
    # w0: 300 - 200 (its probe); w1: 100; w2: 320 - 300
    assert spans.join_host_s_mean(_trace()) == pytest.approx((100 + 100 + 20) / 3 * 1e-9)


def test_nested_spans_of_one_name_count_once():
    t = _trace()
    joins = spans.outermost(spans.starting_in(t.spans, "veer.exec.Join", t.window))
    assert [(s.thread, s.start_ns) for s in joins] == [("w0", 100), ("w1", 500), ("w2", 190)]


def test_a_program_without_the_spans_reads_nothing():
    t = _trace()
    bare = spans.Trace(t.events, [s for s in t.spans if s.stats == {} or "op" in s.stats],
                       t.window)
    # the program's exec.Join spans stay, its phases and pickups are gone
    assert spans.queue_s_p50(bare) is None
    assert spans.probe_wait_s_mean(bare) is None
    assert spans.join_host_s_mean(bare) is None
    harness_only = spans.Trace(t.events, [s for s in t.spans if s.stats == {}], t.window)
    assert spans.join_host_s_mean(harness_only) is None


def test_probe_wait_needs_the_device_programs():
    t = _trace()
    assert spans.probe_wait_s_mean(spans.Trace([], t.spans, t.window)) is None


def test_tiny_traced_run_reads_the_span_metrics(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / ".bench_trace")
    out = tiny.run(tmp_path, trace=True)
    metrics = out.line["metrics"]
    assert metrics["service.queue_s.p50"]["value"] >= 0
    assert metrics["plane.join_host_s.mean"]["value"] > 0
    # on the CPU there is no device program to take from the probe spans
    assert "plane.probe_wait_s.mean" not in metrics
    t = spans.of(out.run)
    assert t is not None and spans.starting_in(t.spans, "veer.service.dequeue", t.window)
    # a trace on disk whose window is not the run's is not read
    out.run.trace = dict(out.run.trace, window_s=out.run.trace["window_s"] + 1.0)
    assert spans.of(out.run) is None
