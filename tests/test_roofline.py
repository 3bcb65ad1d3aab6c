"""Roofline terms of the data plane's kernels: HLO shape bytes, the two
time terms against the published peaks, and unknown devices."""

import pytest

from repro.engine.plane.roofline import Roofline, UnknownDevice, _shape_bytes


def test_shape_bytes():
    assert _shape_bytes("bf16[2048,4096]{1,0}") == 2048 * 4096 * 2
    assert _shape_bytes("f32[8]") == 32
    assert _shape_bytes("pred[2,2]") == 4


def test_roofline_terms_and_bottleneck():
    r = Roofline(flops=1e14, hbm_bytes=1e12, device_kind="TPU v5 lite")
    assert r.t_compute == pytest.approx(1e14 / 197e12)
    assert r.t_memory == pytest.approx(1e12 / 819e9)
    assert r.bottleneck == "memory"
    r = Roofline(flops=1e15, hbm_bytes=1e9, device_kind="TPU v5 lite")
    assert r.bottleneck == "compute"


def test_roofline_unknown_device_is_an_error():
    r = Roofline(flops=1e14, hbm_bytes=1e12, device_kind="cpu")
    assert r.flops == 1e14  # counts stand; no time does
    for term in ("t_compute", "t_memory", "bottleneck"):
        with pytest.raises(UnknownDevice, match="cpu"):
            getattr(r, term)
