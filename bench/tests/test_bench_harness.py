"""One whole run on the CPU (the look for a chip skipped): the last line's
keys, ``correct`` true on the program, false with the timed path broken,
and no result where JAX finds no TPU."""

import importlib.util
import json

import numpy as np
import pytest

from bench.tests import tiny

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_run_is_correct_and_the_line_has_the_result_keys(tmp_path):
    out = tiny.run(tmp_path, control=True)
    line = out.line
    assert list(line) == LINE_KEYS
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 8 and line["failed"] == 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    cell = tiny.tiny_cell()
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    for name, c in line["checks"].items():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"], name
    # the control (the reference in float32 in the program's place) goes
    # through the same comparison and has to come out not correct
    assert out.control["correct"] is False
    assert out.control["readings"]["sink_gap"] > line["checks"]["sink_gap"]["limit"]
    json.dumps(line)


def test_traced_run_reports_per_layer_metrics(tmp_path):
    line = tiny.run(tmp_path, trace=True).line
    assert list(line) == LINE_KEYS[:5] + ["breakdown", "checks"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    names = set(line["metrics"])
    # on the CPU no device trace exists: the device and kernel readers
    # return nothing and the harness leaves them out
    assert {"service.wait_s.p50", "search.verify_s.mean", "executor.exec_s.mean",
            "plane.device_ops_per_version"} <= names
    assert "join_probe_roofline" not in names


def _drop_half_of_each_join(monkeypatch):
    from repro.engine.plane.jax_plane import JaxPlane

    inner = JaxPlane._join

    def half(self, op, inputs):
        left, right = inputs
        return inner(self, op, [left.take(np.arange(len(left) // 2)), right])

    monkeypatch.setattr(JaxPlane, "_join", half)


def _alter_each_aggregate(monkeypatch):
    from repro.engine.plane.jax_plane import JaxPlane

    inner = JaxPlane._aggregate

    def altered(self, op, inputs):
        out = inner(self, op, inputs)
        last = out.order[-1]
        if len(out):
            out.cols[last] = out.cols[last].copy()
            out.cols[last][0] += 1.0
        return out

    monkeypatch.setattr(JaxPlane, "_aggregate", altered)


@pytest.mark.parametrize("fault", [_drop_half_of_each_join, _alter_each_aggregate],
                         ids=["half_the_rows_left_out", "an_answer_altered"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    line = tiny.run(tmp_path).line
    assert line["correct"] is False
    assert line["checks"]["sink_gap"]["value"] > line["checks"]["sink_gap"]["limit"]


def test_no_tpu_means_no_result(capsys):
    spec = importlib.util.spec_from_file_location("bench_run_main", tiny.ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--workload", tiny.CELL, "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "{" not in out.out


def test_latency_counts_versions_answered_after_the_close():
    """The tails are over every version submitted in the window; the rate
    is over those answered before the close."""
    from bench import harness

    def answer(latency, in_window, error=None):
        return harness.Answer(1, False, None, None, t_submit=0.0, t_done=latency,
                              in_window=in_window, error=error)

    answers = [answer(float(s), True) for s in range(1, 10)]
    answers += [answer(100.0, False), answer(5.0, False, error="refused")]
    run = harness.Run(seconds=10.0, setup_s=1.0, answers=answers, failed=1)
    read = {m: harness.load_reader(m) for m in
            ("submit_latency_p50_s", "submit_latency_p90_s", "versions_per_s")}
    assert read["submit_latency_p50_s"](run) == 5.5
    assert read["submit_latency_p90_s"](run) == float(np.percentile(
        list(range(1, 10)) + [100.0], 90))
    assert read["versions_per_s"](run) == 0.9
