"""Delta-cone execution: O(|Δrows|) propagation vs changed-cone recompute.

``exec_bench`` measured what certificate-driven reuse buys over full
re-execution; this one measures what the **delta tier** (ISSUE 10,
``repro.engine.delta``) buys over that reuse path on its target workload:
a 12-version chain of predicate narrow/widen edits near the *top* of a
heavy spine.  Each edit moves a filter threshold above a dominating
downstream filter, so every pair is provably equivalent (the certificate
gate holds) — but the changed operator sits upstream of everything
expensive, so PR 5's exact-tier frontier covers only the source and the
reuse path re-executes the classifier+aggregate cone at full width.  The
delta tier instead evaluates two predicate masks at the boundary and
pushes the resulting row delta through the cone; the delta dies at the
dominating filter and every downstream table is served byte-identically.

Two passes run on identical sources and one warmed verdict cache:

  * **reuse** — ``VersionChainSession`` with ``exec_mode="reuse"``
    (PR 5 behavior: recompute the changed cone, seeded from the
    exact-tier frontier);
  * **delta** — ``exec_mode="delta"`` (the certificate-gated delta tier,
    falling back to the same reuse path when an edit is not amenable).

Self-checking (non-zero exit on violation):

  * every delta-pass sink table is **bit-identical** to an independent
    full re-execution of its version;
  * every pair is verified True and certificate-backed;
  * every pair's execution went through delta rules (``ops_delta > 0``);
  * total delta rows processed ≤ 10% of the input rows the chain saw;
  * end-to-end speedup over the reuse pass ≥ 3x (smoke and full).

Usage (from the repo root):

    python benchmarks/delta_bench.py                  # full sweep (1M rows)
    python benchmarks/delta_bench.py --smoke          # CI: smaller tables +
                                                      #   regression guard vs
                                                      #   BENCH_delta.json
    python benchmarks/delta_bench.py --json OUT.json  # machine-readable rows
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

from repro.api import VeerConfig  # noqa: E402
from repro.core.ev.cache import VerdictCache  # noqa: E402
from repro.engine import (  # noqa: E402
    InMemoryMaterializationStore,
    execute,
    tables_identical,
)
from repro.service import VersionChainSession  # noqa: E402
from repro.workload.flows import narrow_widen_chain, narrow_widen_sources  # noqa: E402

BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "BENCH_delta.json"
# CI guard: the delta/reuse speedup ratio is machine-independent (both
# passes run on the same box in the same process); fail when it regresses
# more than this vs the committed baseline
REGRESSION_TOLERANCE = 0.30

VERSIONS = 12
FULL_ROWS = 1_000_000
SMOKE_ROWS = 150_000
MAX_DELTA_FRACTION = 0.10   # delta rows processed / input rows seen
MIN_SPEEDUP = 3.0           # delta pass vs reuse pass, end to end


def _pass(chain, sources, config, cache):
    """One chain sweep under ``config.exec_mode``; fresh store, shared
    warmed verdict cache.  Returns (reports, wall seconds)."""
    session = VersionChainSession(
        config=config, cache=cache,
        materialization_store=InMemoryMaterializationStore(),
    )
    reports = []
    t0 = time.perf_counter()
    for v in chain:
        reports.append(session.submit(v, sources=sources))
    return reports, time.perf_counter() - t0


def run(versions: int = VERSIONS, rows: int = FULL_ROWS):
    """Returns ``(rows_out, headline)``; raises SystemExit on any identity,
    certification, amenability, or delta-volume violation."""
    config = VeerConfig(evs=("equitas", "spes", "udp"))
    chain = narrow_widen_chain(versions)
    sources = narrow_widen_sources(rows)

    # -- warm the verdict cache: each pair's search is paid once here, so
    # both measured passes see the same (near-zero) verification cost
    cache = VerdictCache()
    warm = VersionChainSession(config=config, cache=cache)
    for v in chain:
        warm.submit(v)

    # -- measured passes: PR 5 cone recompute vs the delta tier
    _, t_reuse = _pass(chain, sources, config.replace(exec_mode="reuse"), cache)
    reports, t_delta = _pass(chain, sources,
                             config.replace(exec_mode="delta"), cache)

    # -- independent full re-executions: the byte-identity oracle
    t0 = time.perf_counter()
    full_results = [execute(v, sources) for v in chain]
    t_full = time.perf_counter() - t0

    # -- audits
    delta_rows_total = 0
    for k, (r, full) in enumerate(zip(reports, full_results)):
        for s, table in full.items():
            if not tables_identical(r.results[s], table):
                raise SystemExit(
                    f"version {k}: delta-pass sink {s} is not bit-identical "
                    f"to a full re-execution"
                )
        if k == 0:
            continue
        if r.verdict is not True or not r.certified:
            raise SystemExit(
                f"pair {k}: verdict {r.verdict} certified={r.certified} — "
                f"the delta tier must only engage on certified equivalence"
            )
        if r.exec_stats.ops_delta <= 0:
            raise SystemExit(
                f"pair {k}: no operator went through a delta rule "
                f"(ops_delta=0) on an amenable narrow/widen edit"
            )
        delta_rows_total += r.exec_stats.delta_rows_processed

    pairs = versions - 1
    delta_fraction = delta_rows_total / (rows * pairs)
    speedup = t_reuse / max(t_delta, 1e-9)

    rows_out = []
    for k, r in enumerate(reports):
        e = r.exec_stats
        rows_out.append(
            {
                "version": k,
                "ops_total": e.ops_total,
                "ops_executed": e.ops_executed,
                "ops_reused": e.ops_reused,
                "ops_delta": e.ops_delta,
                "delta_rows": e.delta_rows_processed,
                "wall_s": round(e.wall_time, 4),
            }
        )
        print(
            f"v{k:>2}: delta {e.ops_delta:>2} ops / "
            f"{e.delta_rows_processed:>8} rows, exec {e.ops_executed:>2}, "
            f"reused {e.ops_reused:>2}, {e.wall_time * 1e3:8.1f} ms"
        )

    headline = {
        "versions": versions,
        "rows": rows,
        "t_reuse_s": round(t_reuse, 4),
        "t_delta_s": round(t_delta, 4),
        "t_full_s": round(t_full, 4),
        "speedup": round(speedup, 3),
        "full_speedup": round(t_full / max(t_delta, 1e-9), 3),
        "delta_rows": delta_rows_total,
        "delta_fraction": round(delta_fraction, 5),
        "ops_delta": sum(r.exec_stats.ops_delta for r in reports),
        "recompute_time_saved_s": round(
            sum(r.exec_stats.recompute_time_saved for r in reports), 4
        ),
        "certified_pairs": sum(int(r.certified) for r in reports[1:]),
    }
    print(
        f"reuse {t_reuse:.2f}s vs delta {t_delta:.2f}s -> {speedup:.1f}x "
        f"(full re-exec {t_full:.2f}s); delta rows "
        f"{delta_rows_total}/{rows * pairs} "
        f"({100 * delta_fraction:.2f}% of input), "
        f"{headline['certified_pairs']}/{pairs} pairs certified, "
        f"identity audit OK"
    )
    if delta_fraction > MAX_DELTA_FRACTION:
        raise SystemExit(
            f"FAIL: delta rules touched {100 * delta_fraction:.1f}% of input "
            f"rows (budget {100 * MAX_DELTA_FRACTION:.0f}%)"
        )
    if speedup < MIN_SPEEDUP:
        raise SystemExit(
            f"FAIL: {speedup:.2f}x < required {MIN_SPEEDUP:.1f}x end-to-end "
            f"speedup over the reuse pass"
        )
    return rows_out, headline


def check_regression(headline, baseline_path: pathlib.Path = BASELINE_PATH) -> bool:
    """CI guard — same scheme as ``exec_bench``: absolute wall clocks are
    runner-dependent, so the committed baseline is compared on the in-run
    delta/reuse **speedup ratio**, with the hard delta-volume and minimum-
    speedup gates enforced unconditionally in ``run``."""
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
        f"FAIL: delta-tier speedup regressed "
        f">{REGRESSION_TOLERANCE:.0%} vs the committed baseline"
    )
    return False


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smaller tables + regression guard vs BENCH_delta.json")
    ap.add_argument("--json", metavar="PATH",
                    help="write rows + headline as JSON (BENCH_<name>.json style)")
    ap.add_argument("--versions", type=int, default=VERSIONS)
    ap.add_argument("--rows", type=int, default=None,
                    help=f"rows in the source table (default {FULL_ROWS}; "
                         f"smoke {SMOKE_ROWS})")
    args = ap.parse_args()

    rows = args.rows or (SMOKE_ROWS if args.smoke else FULL_ROWS)
    rows_out, headline = run(versions=args.versions, rows=rows)

    payload = {
        "name": "delta",
        "smoke": bool(args.smoke),
        "headline": headline,
        "rows": rows_out,
    }
    if args.json:
        pathlib.Path(args.json).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {args.json}")
    if args.smoke and not check_regression(headline):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
