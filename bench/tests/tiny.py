"""A Q40 cell small enough for the CPU tests.

The base filters are widened (prices up to 60, the whole sales window) so
that a few thousand sales rows still reach the sink, and the mix is one
whose pairs verify in milliseconds (window-boundary empty filters and
rename storms), so that a window of a few seconds answers many versions
on the CPU.  The tests steer this here; the benchmark's files are not
touched.
"""

from __future__ import annotations

import json
import pathlib
import time

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "q40.reexec"


def tiny_config(rows: int = 4000) -> dict:
    cfg = json.loads((ROOT / "bench/configs/tpcds_q40_sf1.json").read_text())
    cfg["catalog_sales_rows"] = rows
    cfg["catalog_returns_rows"] = rows // 10
    wide = {"f_price_hi": ["i_current_price", "<=", 60.0],
            "f_date_lo": ["d_date", ">=", 2450815],
            "f_date_hi": ["d_date", "<=", 2452640]}
    for op in cfg["dataflow"]:
        if op["id"] in wide:
            op["pred"] = wide[op["id"]]
    return cfg


def tiny_cell(name: str = CELL, analysts: int = 4) -> harness.Cell:
    cell = harness.load_cell(ROOT, name)
    cell.config = tiny_config()
    cell.traffic = dict(cell.traffic, analysts=analysts,
                        mix={"boundary": 0.5, "rename_storm": 0.5})
    return cell


_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


def run(tmp_path, seed: int = 2**33 + 3, seconds: float = 6.0, trace: bool = False,
        control: bool = False) -> harness.Outcome:
    """One run of the tiny cell; JAX's compile-cache settings, which a run
    sets for its process, are put back for the tests that follow."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    try:
        return harness.run_cell(tiny_cell(), seed, seconds, trace, t0=time.perf_counter(),
                                root=tmp_path, require_tpu=False, control=control)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
