"""The reader of the join's key counter (``plane.codes_direct_share``):
a known answer on given spans, nothing where the program's codes spans
carry no ``keys``, and the counter in a tiny traced Q50 run on the CPU."""

from bench import harness, spans
from bench.tests import tiny_q50

WINDOW = (0.0, 1000.0)
CODES = harness.load_reader("plane.codes_direct_share")


def _span(name, start, dur, **stats):
    return spans.Span("w0", name, float(start), float(dur), stats)


def _run_with(monkeypatch, spans_):
    t = spans.Trace([], [_span("bench.window", *WINDOW)] + spans_, WINDOW)
    monkeypatch.setattr(spans, "of", lambda run: t)
    return harness.Run(seconds=1.0, setup_s=0.0, answers=[], failed=0, trace={})


def test_codes_direct_share_is_one_less_the_sorted_share_of_the_keys(monkeypatch):
    run = _run_with(monkeypatch, [
        _span("veer.plane.join.codes", 10, 30, nl=5, nr=4, keys=3, sorted=0, device=1),
        _span("veer.plane.join.codes", 50, 30, nl=5, nr=4, keys=2, sorted=1, device=0),
        _span("veer.plane.join.codes", 1100, 30, nl=5, nr=4, keys=5, sorted=5),  # after the window
    ])
    assert CODES(run) == 1.0 - 1 / 5


def test_codes_direct_share_reads_nothing_without_the_counter(monkeypatch):
    # the codes spans as a program without the counter opens them
    run = _run_with(monkeypatch, [_span("veer.plane.join.codes", 10, 30, nl=5, nr=4, device=1)])
    assert CODES(run) is None
    untraced = harness.Run(seconds=1.0, setup_s=0.0, answers=[], failed=0)
    assert CODES(untraced) is None


def test_tiny_traced_q50_run_sorts_only_the_wide_key(tmp_path, monkeypatch):
    # the tiny cell keeps SF1's key ranges over 22,000 rows: of the
    # three-key returns join's keys, the customer key (1..100,000) spans
    # more than 4x the rows and takes the sort; every other key the ranks
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / ".bench_trace")
    out = tiny_q50.run(tmp_path, trace=True)
    t = spans.of(out.run)
    codes = [s.stats for s in spans.starting_in(t.spans, "veer.plane.join.codes", t.window)]
    assert codes
    assert all(int(c["sorted"]) == (1 if int(c["keys"]) == 3 else 0) for c in codes)
    keys = sum(int(c["keys"]) for c in codes)
    share = out.line["metrics"]["plane.codes_direct_share"]["value"]
    assert 0 < share < 1 and share == 1.0 - sum(int(c["sorted"]) for c in codes) / keys
