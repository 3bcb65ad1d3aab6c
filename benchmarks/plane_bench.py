"""Data-plane throughput: vectorized jax lowering vs the reference engine.

Three parts, all self-checking (non-zero exit on violation):

  **A. Hot-op chain** — a branched 2-source pipeline over the plane's hot
  operators (fused filter/project, left-outer join on high-cardinality
  keys, classifier, hash-aggregate, distinct, sort) executes on the
  ``numpy`` plane (the per-row dict/loop reference) and on the ``jax``
  plane, from identical sources.  Every sink table must be
  **bit-identical** across planes — the digest/store/certificate contract
  — and the full run requires ≥10x rows/sec from the jax plane at 1M
  left-source rows.

  **B. Certificate-driven session on the jax plane** — a 6-version
  synthetic chain runs execute-with-reuse (``VersionChainSession``,
  in-memory store) entirely on the jax plane.  Sinks must match the
  reference plane's full re-execution byte-for-byte, every pair must be
  certificate-backed, and **every certificate must replay green**
  (``Certificate.replay(registry, P, Q).ok``) — reuse keyed on
  jax-produced bytes is still auditable evidence.

  **C. Roofline report** — the plane's representative jitted kernels
  (filter multiply/mask programs, projection accumulate, join probe) are
  compiled from shapes at the benchmark row count and reported against
  the published peaks of the device they compiled for
  (``repro.engine.plane.roofline.PEAKS``, keyed by ``device_kind``).  A device
  without published peaks (the CPU) gets no roofline terms.

Usage (from the repo root):

    python benchmarks/plane_bench.py           # full: 1M rows, 10x floor
    python benchmarks/plane_bench.py --smoke   # CI: 60k rows + regression
                                               #   guard vs BENCH_plane.json
    python benchmarks/plane_bench.py --json OUT.json
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

from repro.api import VeerConfig  # noqa: E402
from repro.core.ev.cache import VerdictCache  # noqa: E402
from repro.engine import (  # noqa: E402
    InMemoryMaterializationStore,
    Table,
    execute,
    tables_identical,
)
from repro.engine.plane import get_plane  # noqa: E402
from repro.service import VersionChainSession  # noqa: E402
from repro.service.synthetic import make_chain  # noqa: E402
from repro.workload.flows import hot_operator_flow, hot_operator_sources  # noqa: E402

BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "BENCH_plane.json"
# CI guard: absolute wall-clock is runner-dependent; the committed baseline
# is compared on the in-run numpy/jax speedup ratio (same box, same process)
REGRESSION_TOLERANCE = 0.30

FULL_ROWS = 1_000_000
SMOKE_ROWS = 60_000
MIN_SPEEDUP_FULL = 10.0
SESSION_VERSIONS = 6
SESSION_ROWS = 20_000


# -- part A: hot-op chain -------------------------------------------------------


def run_chain(rows: int):
    dag = hot_operator_flow()
    sources = hot_operator_sources(rows)

    # warm the jax plane at full size first: jit specializes per operand
    # shape, so the compile (a one-time process cost the numpy plane has
    # no analogue of) is excluded from the measurement, like any warmup
    warm = hot_operator_sources(rows, seed=1)
    execute(dag, warm, plane="jax")

    t0 = time.perf_counter()
    ref = execute(dag, sources, plane="numpy")
    t_numpy = time.perf_counter() - t0

    t0 = time.perf_counter()
    jx = execute(dag, sources, plane="jax")
    t_jax = time.perf_counter() - t0

    for s in ref:
        if not tables_identical(ref[s], jx[s]):
            raise SystemExit(
                f"FAIL: sink {s} differs between the numpy and jax planes"
            )
    speedup = t_numpy / max(t_jax, 1e-9)
    headline = {
        "rows": rows,
        "t_numpy_s": round(t_numpy, 4),
        "t_jax_s": round(t_jax, 4),
        "numpy_rows_per_s": round(rows / max(t_numpy, 1e-9)),
        "jax_rows_per_s": round(rows / max(t_jax, 1e-9)),
        "speedup": round(speedup, 3),
        "sinks_identical": True,
    }
    print(
        f"chain @ {rows} rows: numpy {t_numpy:.2f}s vs jax {t_jax:.2f}s "
        f"-> {speedup:.1f}x, sinks bit-identical"
    )
    return headline


# -- part B: certificate-driven session on the jax plane ------------------------


def run_session(rows: int = SESSION_ROWS, versions: int = SESSION_VERSIONS):
    from repro.api.registry import default_registry

    config = VeerConfig(evs=("equitas", "spes", "udp"), plane="jax")
    chain = make_chain(versions, heavy=True)
    rng = np.random.default_rng(0)
    sources = {
        sid: Table(
            {
                c: rng.integers(0, 7, rows).astype(np.float64)
                for c in chain[0].ops[sid].get("schema")
            },
            list(chain[0].ops[sid].get("schema")),
        )
        for sid in chain[0].sources
    }

    full = [execute(v, sources) for v in chain]  # reference ground truth

    cache = VerdictCache()
    warm = VersionChainSession(config=config, cache=cache)
    for v in chain:
        warm.submit(v)

    session = VersionChainSession(
        config=config,
        cache=cache,
        materialization_store=InMemoryMaterializationStore(),
    )
    reports = [session.submit(v, sources=sources) for v in chain]

    registry = default_registry()
    replayed = 0
    for k, (r, truth) in enumerate(zip(reports, full)):
        for s, table in truth.items():
            if not tables_identical(r.results[s], table):
                raise SystemExit(
                    f"FAIL: session v{k} sink {s} (jax plane) differs from "
                    f"the reference plane's full re-execution"
                )
        if k == 0:
            continue
        if not r.certified or r.certificate is None:
            raise SystemExit(f"FAIL: pair {k} is not certificate-backed")
        rep = r.certificate.replay(registry, chain[k - 1], chain[k])
        if not rep.ok:
            raise SystemExit(f"FAIL: pair {k} certificate replay: {rep.summary()}")
        replayed += 1

    lowered = sum(r.exec_stats.ops_lowered for r in reports if r.exec_stats)
    headline = {
        "session_versions": versions,
        "session_rows": rows,
        "certified_pairs": replayed,
        "certificates_replayed_ok": replayed,
        "replay_fraction": 1.0,
        "ops_lowered": lowered,
    }
    print(
        f"session (jax plane): {versions} versions, {replayed}/{versions - 1} "
        f"certificates replayed green, {lowered} ops lowered, sinks identical"
    )
    if lowered == 0:
        raise SystemExit("FAIL: the jax plane lowered no operators")
    return headline


# -- part C: roofline report ----------------------------------------------------


def run_roofline(rows: int):
    import jax

    from repro.engine.plane.roofline import PEAKS

    dev = jax.devices()[0]
    where = f"{dev.platform} / {dev.device_kind} x{len(jax.devices())}"
    if dev.device_kind not in PEAKS:
        print(f"roofline @ {rows} rows: no published peaks for {where}; skipped")
        return []
    report = get_plane("jax").roofline_report(rows)
    print(f"roofline @ {rows} rows on {where}:")
    for r in report:
        print(
            f"  {r['kernel']:<12} flops {r['flops']:>12.3g}  "
            f"bytes {r['hbm_bytes']:>12.3g}  t_mem {r['t_memory_s']:.2e}s  "
            f"t_comp {r['t_compute_s']:.2e}s  -> {r['bottleneck']}"
        )
    return report


# -- driver ---------------------------------------------------------------------


def check_regression(headline, baseline_path: pathlib.Path = BASELINE_PATH) -> bool:
    """CI guard — same scheme as ``exec_bench``: compare the committed
    baseline on the in-run speedup ratio, not wall-clock."""
    if not baseline_path.exists():
        print(f"no committed baseline at {baseline_path}; skipping guard")
        return True
    baseline = json.loads(baseline_path.read_text())["headline"]
    floor = baseline["speedup"] * (1.0 - REGRESSION_TOLERANCE)
    print(
        f"regression guard: speedup {headline['speedup']:.2f}x vs committed "
        f"{baseline['speedup']:.2f}x (floor {floor:.2f}x)"
    )
    if headline["speedup"] >= floor:
        return True
    print(
        f"FAIL: jax-plane chain speedup regressed "
        f">{REGRESSION_TOLERANCE:.0%} vs the committed baseline"
    )
    return False


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smaller tables + regression guard vs BENCH_plane.json")
    ap.add_argument("--json", metavar="PATH",
                    help="write headline + roofline as JSON")
    ap.add_argument("--rows", type=int, default=None,
                    help=f"left-source rows (default {FULL_ROWS}; "
                         f"smoke {SMOKE_ROWS})")
    args = ap.parse_args()
    from repro.engine.plane.jax_plane import use_compile_cache

    use_compile_cache(ROOT)
    rows = args.rows or (SMOKE_ROWS if args.smoke else FULL_ROWS)
    headline = run_chain(rows)
    headline.update(run_session())
    roofline = run_roofline(rows)
    headline["bandwidth_bound_kernels"] = sum(
        r["bandwidth_bound"] for r in roofline
    )

    payload = {
        "name": "plane",
        "smoke": bool(args.smoke),
        "headline": headline,
        "roofline": roofline,
    }
    if args.json:
        pathlib.Path(args.json).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {args.json}")
    if args.smoke:
        if not check_regression(headline):
            raise SystemExit(1)
    elif headline["speedup"] < MIN_SPEEDUP_FULL:
        raise SystemExit(
            f"FAIL: {headline['speedup']:.2f}x < required "
            f"{MIN_SPEEDUP_FULL:.1f}x jax-plane speedup at {rows} rows"
        )


if __name__ == "__main__":
    main()
