"""End-to-end chain execution: certificate-driven reuse vs full re-execution.

Earlier benchmarks measured *verification* time; this one measures what
verification buys — **end-to-end pipeline time**.  A 12-version
iterative-analytics chain (``repro.service.synthetic.make_chain`` with the
heavy classifier+aggregate tails) runs two ways on identical sources:

  * **full**  — every version executes every operator (``repro.engine
    .execute``), the pre-reuse behavior;
  * **reuse** — a ``VersionChainSession`` with an operator-level
    materialization store and a *warmed verdict cache*: v1 executes fully
    and materializes, each successor verifies against its predecessor
    (warm: ~zero EV calls), derives the reuse frontier from the pair's
    replay-green certificate, and recomputes only the changed cone.

The headline uses the in-memory store (the hot serving tier a production
service keeps materializations in; byte-budget LRU bounds it); the full
sweep additionally reports the persistent ``DiskMaterializationStore``
variant, whose round-trip fidelity is property-tested in
``tests/test_exec_reuse.py``.

Self-checking (non-zero exit on violation):

  * every reuse-run sink table is **bit-identical** to the full run's;
  * every version that reused anything is certificate-backed;
  * ≤ 30% of all chain operators execute in reuse mode;
  * (full sweep) end-to-end speedup ≥ 3x.

Usage (from the repo root):

    python benchmarks/exec_bench.py                  # full 12-version sweep
    python benchmarks/exec_bench.py --smoke          # CI: smaller tables +
                                                     #   regression guard vs
                                                     #   BENCH_exec.json
    python benchmarks/exec_bench.py --json OUT.json  # machine-readable rows
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

from repro.api import VeerConfig  # noqa: E402
from repro.core.ev.cache import VerdictCache  # noqa: E402
from repro.engine import (  # noqa: E402
    DiskMaterializationStore,
    InMemoryMaterializationStore,
    Table,
    execute,
    tables_identical,
)
from repro.service import VersionChainSession  # noqa: E402
from repro.service.synthetic import make_chain  # noqa: E402

BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "BENCH_exec.json"
# CI guard: the speedup ratio is machine-independent (both sides run on the
# same box in the same process); fail when it regresses more than this
REGRESSION_TOLERANCE = 0.30

VERSIONS = 12           # the acceptance workload: 12-version chain
FULL_ROWS = 30000
SMOKE_ROWS = 8000
MAX_EXEC_FRACTION = 0.30
MIN_SPEEDUP_FULL = 3.0


def _sources(version, rows: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    out = {}
    for sid in version.sources:
        schema = version.ops[sid].get("schema")
        out[sid] = Table(
            {c: rng.integers(0, 7, rows).astype(np.float64) for c in schema},
            list(schema),
        )
    return out


def _reuse_pass(chain, sources, config, cache, store):
    """One execute-with-reuse sweep; returns (reports, wall, chain report,
    store stats)."""
    session = VersionChainSession(
        config=config, cache=cache, materialization_store=store
    )
    reports = []
    t0 = time.perf_counter()
    for v in chain:
        reports.append(session.submit(v, sources=sources))
    wall = time.perf_counter() - t0
    return reports, wall, session.report(), store.stats()


def run(versions: int = VERSIONS, rows: int = FULL_ROWS, disk: bool = True):
    """Returns ``(rows_out, headline)``; raises SystemExit on any identity
    or certification violation (reuse must be a pure performance change)."""
    config = VeerConfig(evs=("equitas", "spes", "udp"))
    chain = make_chain(versions, heavy=True)
    sources = _sources(chain[0], rows)
    ops_per_version = len(chain[0].ops)

    # -- full re-execution baseline
    t0 = time.perf_counter()
    full_results = [execute(v, sources) for v in chain]
    t_full = time.perf_counter() - t0

    # -- warm the verdict cache (the steady-state production setting: the
    # chain's window questions were all paid for by earlier traffic)
    cache = VerdictCache()
    warm = VersionChainSession(config=config, cache=cache)
    for v in chain:
        warm.submit(v)

    # -- execute with reuse on the warmed cache (headline: in-memory tier)
    reports, t_reuse, report, store_stats = _reuse_pass(
        chain, sources, config, cache, InMemoryMaterializationStore()
    )

    # -- audits
    for k, (r, full) in enumerate(zip(reports, full_results)):
        for s, table in full.items():
            if not tables_identical(r.results[s], table):
                raise SystemExit(
                    f"version {k}: reused sink {s} is not bit-identical "
                    f"to the full re-execution"
                )
        if k > 0 and r.exec_stats.ops_reused and not r.certified:
            raise SystemExit(
                f"version {k}: reused {r.exec_stats.ops_reused} ops "
                f"without a certificate"
            )

    # -- secondary: the persistent disk store (reported, not gated — npz
    # serialization cost is a property of the backing tier, not the engine)
    t_disk = None
    if disk:
        with tempfile.TemporaryDirectory(prefix="veer_exec_bench_") as tmp:
            disk_reports, t_disk, _, _ = _reuse_pass(
                chain, sources, config, cache, DiskMaterializationStore(tmp)
            )
            for r, full in zip(disk_reports, full_results):
                for s, table in full.items():
                    if not tables_identical(r.results[s], table):
                        raise SystemExit(
                            "disk-store pass lost bit-identity at sink "
                            f"{s}"
                        )

    total_ops = ops_per_version * versions
    executed = report.total_ops_executed
    exec_fraction = executed / total_ops
    speedup = t_full / max(t_reuse, 1e-9)

    rows_out = []
    all_exec = [report.initial_exec] + [r.exec_stats for r in report.pairs]
    for k, e in enumerate(all_exec):
        rows_out.append(
            {
                "version": k,
                "ops_total": e.ops_total,
                "ops_executed": e.ops_executed,
                "ops_reused": e.ops_reused,
                "tables_served": e.tables_served,
                "peak_live_tables": e.peak_live_tables,
                "wall_s": round(e.wall_time, 4),
            }
        )
        print(
            f"v{k:>2}: exec {e.ops_executed:>3}/{e.ops_total} ops, "
            f"reused {e.ops_reused:>3}, served {e.tables_served:>3}, "
            f"peak {e.peak_live_tables:>2} live, {e.wall_time * 1e3:8.1f} ms"
        )

    headline = {
        "versions": versions,
        "rows": rows,
        "ops_per_version": ops_per_version,
        "t_full_s": round(t_full, 4),
        "t_reuse_s": round(t_reuse, 4),
        "t_reuse_disk_s": round(t_disk, 4) if t_disk is not None else None,
        "disk_speedup": (
            round(t_full / t_disk, 3) if t_disk is not None else None
        ),
        "speedup": round(speedup, 3),
        "exec_fraction": round(exec_fraction, 4),
        "ops_executed": executed,
        "ops_total": total_ops,
        "tables_served": report.total_tables_served,
        "recompute_time_saved_s": round(
            sum(e.recompute_time_saved for e in all_exec), 4
        ),
        "store_dedup_skipped": store_stats["dedup_skipped_writes"],
        "certified_pairs": report.certified_pairs,
    }
    print(
        f"full {t_full:.2f}s vs reuse {t_reuse:.2f}s -> {speedup:.1f}x ; "
        f"executed {executed}/{total_ops} ops "
        f"({100 * exec_fraction:.0f}%), {report.total_tables_served} tables "
        f"served, {report.certified_pairs}/{versions - 1} pairs certified, "
        f"identity audit OK"
        + (f" ; disk store {t_full / t_disk:.1f}x" if t_disk else "")
    )
    if exec_fraction > MAX_EXEC_FRACTION:
        raise SystemExit(
            f"FAIL: executed {100 * exec_fraction:.0f}% of operators "
            f"(budget {100 * MAX_EXEC_FRACTION:.0f}%)"
        )
    return rows_out, headline


def check_regression(headline, baseline_path: pathlib.Path = BASELINE_PATH) -> bool:
    """CI guard — mirrors search_bench: an absolute wall-clock number is
    runner-dependent, so the committed baseline is compared on the in-run
    **speedup ratio** (same machine, same process, both sides), with the
    hard exec-fraction budget enforced unconditionally in ``run``."""
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
        f"FAIL: end-to-end reuse speedup regressed "
        f">{REGRESSION_TOLERANCE:.0%} vs the committed baseline"
    )
    return False


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smaller tables + regression guard vs BENCH_exec.json")
    ap.add_argument("--json", metavar="PATH",
                    help="write rows + headline as JSON (BENCH_<name>.json style)")
    ap.add_argument("--versions", type=int, default=VERSIONS)
    ap.add_argument("--rows", type=int, default=None,
                    help="rows per source table (default 6000; smoke 2500)")
    args = ap.parse_args()

    rows = args.rows or (SMOKE_ROWS if args.smoke else FULL_ROWS)
    rows_out, headline = run(
        versions=args.versions, rows=rows, disk=not args.smoke
    )

    payload = {
        "name": "exec",
        "smoke": bool(args.smoke),
        "headline": headline,
        "rows": rows_out,
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
            f"{MIN_SPEEDUP_FULL:.1f}x end-to-end speedup"
        )


if __name__ == "__main__":
    main()
