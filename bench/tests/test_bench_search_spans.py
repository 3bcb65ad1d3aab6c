"""The readers of the search's counters and EV spans
(``search.sat_calls.mean``, ``ev.check_s.mean``): known answers on given
spans, nothing where the program opens no such span or sets no such
counter, and values from a tiny traced Q50 run on the CPU."""

from bench import harness, spans
from bench.tests import tiny_q50

WINDOW = (0.0, 1000.0)
SAT = harness.load_reader("search.sat_calls.mean")
CHECK = harness.load_reader("ev.check_s.mean")


def _span(name, start, dur, **stats):
    return spans.Span("w0", name, float(start), float(dur), stats)


def _run_with(monkeypatch, spans_):
    t = spans.Trace([], [_span("bench.window", *WINDOW)] + spans_, WINDOW)
    monkeypatch.setattr(spans, "of", lambda run: t)
    return harness.Run(seconds=1.0, setup_s=0.0, answers=[], failed=0, trace={})


def test_the_readers_average_the_spans_that_start_in_the_window(monkeypatch):
    run = _run_with(monkeypatch, [
        _span("veer.search.decide", 10, 300, sat_calls=40, verdict="unk"),
        _span("veer.search.decide", 400, 5, sat_calls=0, verdict="eq", reused=1),
        _span("veer.search.decide", 1100, 5, sat_calls=999),  # after the window
        _span("veer.ev.check", 20, 2e6, ev="equitas", ops=9, verdict="unk"),
        _span("veer.ev.check", 30, 4e6, ev="spes", ops=9, verdict="neq"),
        _span("veer.ev.check", 1200, 9e9, ev="spes", ops=9, verdict="neq"),
    ])
    assert SAT(run) == 20.0
    assert CHECK(run) == 3e6 / 1e9


def test_a_program_without_the_counter_or_the_span_reads_nothing(monkeypatch):
    run = _run_with(monkeypatch, [_span("veer.search.decide", 10, 300, verdict="unk", reused=0)])
    assert SAT(run) is None and CHECK(run) is None
    untraced = harness.Run(seconds=1.0, setup_s=0.0, answers=[], failed=0)
    assert SAT(untraced) is None and CHECK(untraced) is None


def test_tiny_traced_q50_run_reads_both(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / ".bench_trace")
    metrics = tiny_q50.run(tmp_path, trace=True).line["metrics"]
    assert metrics["search.sat_calls.mean"]["value"] > 0
    assert metrics["ev.check_s.mean"]["value"] > 0
