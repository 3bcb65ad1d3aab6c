"""BENCHMARK.json keeps to the benchmark's format rules, and every name in it
resolves to its file."""

import json
import re

import pytest

from bench import harness
from bench.tests.tiny import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_loads_config_and_mix_by_name(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    cell = harness.load_cell(ROOT, w["name"])
    assert cell.config["name"] == w["config"]
    assert cell.traffic["mix"] and cell.traffic["analysts"] > 0
    assert w["chips"] == cell.chips == 1
    assert len(w["why"]) <= 200
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer


def test_configs():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg and not key.endswith(("_dim", "_rank"))


def test_metrics_resolve_and_names():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(harness.load_reader(m["name"]))


def test_peaks_table():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["source"] == "Google Cloud documentation, TPU v5e"
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")


def test_a_mix_key_the_harness_does_not_read_is_refused(tmp_path, monkeypatch):
    mix = json.loads((ROOT / "bench/traffic/reexec.json").read_text())
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "reexec.json").write_text(json.dumps(dict(mix, loop="open")))
    monkeypatch.setattr(harness, "BENCH", tmp_path)
    with pytest.raises(ValueError, match="loop"):
        harness.load_cell(ROOT, "q40.reexec")
