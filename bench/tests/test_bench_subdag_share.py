"""The reader of the search's sub-DAG counter (``search.subdag_share``):
known answers on given spans, nothing where the program's decide spans
carry no ``windows`` and ``subdags`` or admit no window, and the share in
a tiny traced Q50 run on the CPU, where the EV checks still run in the
traced process."""

import pytest

from bench import harness, spans
from bench.tests import tiny_q50
from bench.tests.tiny import ROOT

WINDOW = (0.0, 1000.0)
SUBDAGS = harness.load_reader("search.subdag_share")


def _span(name, start, dur, **stats):
    return spans.Span("w0", name, float(start), float(dur), stats)


def _run_with(monkeypatch, spans_):
    t = spans.Trace([], [_span("bench.window", *WINDOW)] + spans_, WINDOW)
    monkeypatch.setattr(spans, "of", lambda run: t)
    return harness.Run(seconds=1.0, setup_s=0.0, answers=[], failed=0, trace={})


def test_subdag_share_sums_the_decide_spans_that_start_in_the_window(monkeypatch):
    run = _run_with(monkeypatch, [
        _span("veer.search.decide", 10, 300, windows=3000, subdags=3, verdict="eq", reused=0),
        _span("veer.search.decide", 400, 5, windows=0, subdags=0, verdict="eq", reused=1),
        _span("veer.search.decide", 500, 50, windows=1000, subdags=0, verdict="unk", reused=0),
        _span("veer.search.decide", 1100, 5, windows=10, subdags=10),  # after the window
    ])
    assert SUBDAGS(run) == 3 / 4000


@pytest.mark.parametrize("decide", [
    {"verdict": "unk", "reused": 0, "sat_calls": 40},            # a program without the counters
    {"verdict": "eq", "reused": 1, "windows": 0, "subdags": 0},  # pair-cache answers only
], ids=["no-counters", "no-windows"])
def test_subdag_share_reads_nothing_without_admitted_windows(monkeypatch, decide):
    run = _run_with(monkeypatch, [_span("veer.search.decide", 10, 300, **decide)])
    assert SUBDAGS(run) is None
    untraced = harness.Run(seconds=1.0, setup_s=0.0, answers=[], failed=0)
    assert SUBDAGS(untraced) is None


def test_tiny_traced_q50_run_builds_sub_dags_for_few_windows(tmp_path, monkeypatch):
    # the tiny cell's own mix decides each pair by one of some twenty
    # admitted windows, a share near 0.05 by construction; half the mix
    # here is the benchmark's own semantic and predicate edits, whose
    # searches admit thousands of windows and build sub-DAGs for none
    tiny_cell = tiny_q50.tiny_cell

    def mixed_cell():
        cell = tiny_cell()
        reexec = harness.load_cell(ROOT, "q50.reexec").traffic["mix"]
        mix = {k: v / 2 for k, v in cell.traffic["mix"].items()}
        mix.update({k: v / 2 for k, v in reexec.items()})
        cell.traffic = dict(cell.traffic, mix=mix)
        return cell

    monkeypatch.setattr(tiny_q50, "tiny_cell", mixed_cell)
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / ".bench_trace")
    metrics = tiny_q50.run(tmp_path, trace=True).line["metrics"]
    assert 0 <= metrics["search.subdag_share"]["value"] <= 0.05
    assert metrics["ev.check_s.mean"]["value"] > 0
