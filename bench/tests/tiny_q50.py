"""A Q50 cell small enough for the CPU tests.

The returned-month filter is widened to the whole year 2001, so that a
few thousand sales rows still give every one of the five aging buckets a
row per store, and the mix is one whose pairs verify in milliseconds
(window-boundary empty filters and rename storms).  The benchmark's files
are not touched.
"""

from __future__ import annotations

import json
import time

from bench import harness
from bench.tests import tiny
from bench.tests.tiny import ROOT

CELL = "q50.reexec"


def tiny_config(rows: int = 20000) -> dict:
    cfg = json.loads((ROOT / "bench/configs/tpcds_q50_sf1.json").read_text())
    cfg["store_sales_rows"] = rows
    cfg["store_returns_rows"] = rows // 10
    for op in cfg["dataflow"]:
        if op["id"] == "f_moy":
            op["pred"] = ["r_d_moy", ">=", 1]
    return cfg


def tiny_cell(analysts: int = 4) -> harness.Cell:
    cell = harness.load_cell(ROOT, CELL)
    cell.config = tiny_config()
    cell.traffic = dict(cell.traffic, analysts=analysts,
                        mix={"boundary": 0.5, "rename_storm": 0.5})
    return cell


def run(tmp_path, seed: int = 2**33 + 5, seconds: float = 6.0, trace: bool = False,
        control: bool = False) -> harness.Outcome:
    """One run of the tiny cell, JAX's compile-cache settings put back
    afterwards, as ``tiny.run`` does."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in tiny._CACHE_OPTIONS}
    try:
        return harness.run_cell(tiny_cell(), seed, seconds, trace, t0=time.perf_counter(),
                                root=tmp_path, require_tpu=False, control=control)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
