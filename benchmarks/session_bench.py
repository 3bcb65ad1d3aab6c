"""Sustained edit-session stress benchmark with differential oracles.

Generates seeded adversarial edit sessions (``repro.workload``) over the
W1-W8 shapes — Calcite-preserving rewrites, semantic edits, window-boundary
splices, rename storms, churn/revert sequences — and replays them as
concurrent traffic through a ``VerificationService``.  Every answer is
cross-checked: EQ verdicts must be byte-identical under execution,
expected-equivalent pairs must never come back NEQ, every decided pair's
certificate must replay green bound to its pair.  The run FAILS (exit 1)
on any oracle violation — this is a correctness harness first and a
throughput benchmark second.

Reported: pairs/sec, p50/p99 pair latency, verified fraction, window- and
pair-cache hits, and the speedup over a sequential no-sharing baseline
(each session replayed alone on fresh caches — the machine-independent
ratio the CI guard falls back to).

Usage (from the repo root):

    python benchmarks/session_bench.py                # default profile
    python benchmarks/session_bench.py --smoke        # CI: 200 pairs over 8
                                                      #   clients + >30%
                                                      #   regression guard vs
                                                      #   BENCH_session.json
    python benchmarks/session_bench.py --extended     # nightly-ish profile
    python benchmarks/session_bench.py --json OUT.json
    python benchmarks/session_bench.py --dump-windows corpus.jsonl
                                                      # labeled-window corpus
                                                      #   for the learned-
                                                      #   scorer roadmap item
    python benchmarks/session_bench.py --exec-mode delta
                                                      # delta-cone execution
                                                      #   under the full bit-
                                                      #   identity oracle
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

from repro.service import VersionChainSession  # noqa: E402
from repro.workload import (  # noqa: E402
    SessionGenerator,
    WorkloadConfig,
    default_veer_config,
    dump_windows,
    extended_config,
    replay_sessions,
    smoke_config,
)

BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "BENCH_session.json"
# CI guard: fail when pairs/sec drops more than this vs the committed baseline
REGRESSION_TOLERANCE = 0.30

# default (non-smoke) profile: a middle ground between smoke and extended
DEFAULT_CONFIG = WorkloadConfig(sessions=8, clients=8, chain_length=16,
                                max_decompositions=150)


def sequential_baseline(sessions, config) -> dict:
    """Replay each session alone on fresh caches (no sharing of any kind):
    the no-service cost of the same traffic.  The service/sequential ratio
    is measured in-run on the same machine, so the CI guard can fall back
    to it when absolute pairs/sec is hardware-skewed."""
    veer_config = default_veer_config(config)
    pairs = 0
    t0 = time.perf_counter()
    for s in sessions:
        with VersionChainSession(config=veer_config) as session:
            for k, v in enumerate(s.versions):
                session.submit(v, s.pairs[k - 1].mapping if k > 0 else None)
            pairs += len(session.report().pairs)
    wall = time.perf_counter() - t0
    return {"pairs": pairs, "wall_s": wall,
            "pairs_per_sec": pairs / max(wall, 1e-9)}


def delta_eligible_census(sessions) -> dict:
    """Label every planned pair with its delta-amenability class.

    Uses ``repro.core.delta.delta_census`` on each consecutive version
    pair (same analysis the delta tier runs after verification, minus the
    certificate gate): amenable pairs count under their class (narrow /
    widen / filter-general / project-cols / agg-swap), ineligible pairs
    under their ``fallback:*`` reason — the census the ISSUE 10 satellite
    reports so a workload's delta coverage is visible at a glance."""
    from repro.core.delta import delta_census

    census: dict = {}
    for s in sessions:
        for k, _ in enumerate(s.pairs):
            P, Q = s.versions[k], s.versions[k + 1]
            _, label = delta_census(P, Q, s.pairs[k].mapping)
            census[label] = census.get(label, 0) + 1
    return census


def run(config: WorkloadConfig, *, exec_reuse: bool = False,
        collect_windows: bool = False, baseline: bool = True):
    """Generate + replay one profile; returns ``(result, headline, rows)``.

    Raises ``SystemExit`` on oracle violations or service errors — a stress
    run that caught a real divergence must never report success.
    """
    t0 = time.perf_counter()
    sessions = SessionGenerator(config).generate()
    gen_wall = time.perf_counter() - t0
    n_pairs = sum(len(s.pairs) for s in sessions)
    families = {}
    for s in sessions:
        for p in s.pairs:
            families[p.kind] = families.get(p.kind, 0) + 1
    print(
        f"generated {len(sessions)} sessions / {n_pairs} pairs "
        f"in {gen_wall:.2f}s  (families: "
        + ", ".join(f"{k}={v}" for k, v in sorted(families.items())) + ")"
    )
    delta_census = delta_eligible_census(sessions)
    eligible = sum(
        v for k, v in delta_census.items() if not k.startswith("fallback:")
    )
    print(
        f"delta-eligible census: {eligible}/{n_pairs} pairs amenable  ("
        + ", ".join(f"{k}={v}" for k, v in sorted(delta_census.items())) + ")"
    )

    result = replay_sessions(
        sessions, config, exec_reuse=exec_reuse,
        collect_windows=collect_windows,
    )
    print(result.summary())
    if not result.ok:
        raise SystemExit(
            f"ORACLE FAILURE: {len(result.violations)} violations, "
            f"{len(result.errors)} service errors"
        )

    seq = None
    if baseline:
        seq = sequential_baseline(sessions, config)
        print(
            f"sequential baseline: {seq['pairs_per_sec']:.1f} pairs/s "
            f"({seq['wall_s']:.2f}s, fresh caches, no sharing)"
        )

    headline = {
        "seed": config.seed,
        "sessions": config.sessions,
        "clients": config.clients,
        "pairs": result.pairs,
        "pairs_per_sec": result.pairs_per_sec,
        "p50_latency_ms": result.p50_latency * 1e3,
        "p99_latency_ms": result.p99_latency * 1e3,
        "verified_fraction": result.verified_fraction,
        "reused_pairs": result.reused,
        "certified_pairs": result.certified,
        "violations": len(result.violations),
        "busy_rejections": result.busy_rejections,
        "cache_hits": result.cache_stats.get("hits", 0),
        "pair_cache_hits": result.pair_cache_stats.get("hits", 0),
        "ops_delta": result.ops_delta,
        "delta_rows": result.delta_rows,
        "recompute_saved_s": result.recompute_saved_s,
        "speedup": (
            result.pairs_per_sec / max(seq["pairs_per_sec"], 1e-9)
            if seq else None
        ),
    }
    rows = {
        "verdicts": result.verdicts,
        "families": families,
        "delta_census": delta_census,
        "gen_wall_s": gen_wall,
        "run_wall_s": result.run_wall,
        "oracle_wall_s": result.oracle_wall,
        "sequential": seq,
        "cache_stats": result.cache_stats,
        "pair_cache_stats": result.pair_cache_stats,
    }
    print(
        f"headline: {headline['pairs']} pairs @ "
        f"{headline['pairs_per_sec']:.1f} pairs/s, "
        f"p50 {headline['p50_latency_ms']:.0f} ms, "
        f"p99 {headline['p99_latency_ms']:.0f} ms, "
        f"verified {100 * headline['verified_fraction']:.0f}%"
        + (f", speedup {headline['speedup']:.1f}x" if seq else "")
    )
    return result, headline, rows


def check_regression(headline, baseline_path: pathlib.Path = BASELINE_PATH) -> bool:
    """CI guard: pairs/sec vs the committed baseline, with the
    machine-independent service/sequential speedup as the fallback (same
    scheme as ``search_bench.check_regression``)."""
    if not baseline_path.exists():
        print(f"no committed baseline at {baseline_path}; skipping guard")
        return True
    baseline = json.loads(baseline_path.read_text())["headline"]
    floor = baseline["pairs_per_sec"] * (1.0 - REGRESSION_TOLERANCE)
    rate = headline["pairs_per_sec"]
    print(
        f"regression guard: {rate:.1f} pairs/s vs committed "
        f"{baseline['pairs_per_sec']:.1f} (floor {floor:.1f})"
    )
    if rate >= floor:
        return True
    if headline.get("speedup") is None or baseline.get("speedup") is None:
        print("FAIL: below floor and no speedup ratio to fall back to")
        return False
    speedup_floor = baseline["speedup"] * (1.0 - REGRESSION_TOLERANCE)
    print(
        f"  below absolute floor; checking machine-independent speedup: "
        f"{headline['speedup']:.2f}x vs committed {baseline['speedup']:.2f}x "
        f"(floor {speedup_floor:.2f}x)"
    )
    if headline["speedup"] >= speedup_floor:
        print("  speedup held — slower runner, not a service regression")
        return True
    print(
        f"FAIL: pairs/sec AND service speedup both regressed "
        f">{REGRESSION_TOLERANCE:.0%} vs the committed baseline"
    )
    return False


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile (200 pairs, 8 clients) + regression "
                         "guard vs BENCH_session.json")
    ap.add_argument("--extended", action="store_true",
                    help="nightly-ish profile (longer chains, deeper budget)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", metavar="PATH",
                    help="write headline + rows as JSON "
                         "(the committed baseline is benchmarks/BENCH_session.json)")
    ap.add_argument("--dump-windows", metavar="PATH",
                    help="write the labeled-window corpus as JSON lines")
    ap.add_argument("--exec-reuse", action="store_true",
                    help="route versions through certificate-seeded partial "
                         "execution and add the bit-identity oracle")
    ap.add_argument("--exec-mode", choices=("full", "reuse", "delta"),
                    default="reuse",
                    help="execution mode of the replayed sessions "
                         "(VeerConfig.exec_mode); 'delta' propagates row "
                         "deltas through amenable changed cones and implies "
                         "--exec-reuse so every served sink is checked "
                         "bit-identical against a fresh full execution")
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the sequential no-sharing baseline")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="replay through a VerificationFleet of N worker "
                         "processes instead of the threaded service (the "
                         "differential oracles and certificate replay audit "
                         "stay on; see docs/SCALE_OUT.md)")
    ap.add_argument("--shared-tier", choices=("local", "remote"),
                    default="local",
                    help="fleet cache tier (remote = file-backed FileTier "
                         "in a temp dir)")
    ap.add_argument("--plane", default="numpy",
                    help="data plane for the replayed sessions (numpy|jax); "
                         "the differential oracle stays on the reference "
                         "plane, so a non-default plane cross-checks every "
                         "sink byte-for-byte")
    args = ap.parse_args()
    if args.smoke and args.extended:
        raise SystemExit("--smoke and --extended are mutually exclusive")

    if args.smoke:
        config = smoke_config(args.seed)
    elif args.extended:
        config = extended_config(args.seed)
    else:
        config = DEFAULT_CONFIG.replace(seed=args.seed)
    config = config.replace(plane=args.plane, fleet=args.fleet,
                            shared_tier=args.shared_tier,
                            exec_mode=args.exec_mode).validate()
    if args.plane == "jax":
        from repro.engine.plane.jax_plane import use_compile_cache

        use_compile_cache(ROOT)

    result, headline, rows = run(
        config,
        exec_reuse=args.exec_reuse or args.exec_mode == "delta",
        collect_windows=bool(args.dump_windows),
        baseline=not args.no_baseline,
    )

    if args.dump_windows:
        with open(args.dump_windows, "w") as fh:
            report = dump_windows(result.windows, fh)
        print(f"wrote {args.dump_windows}: {report.summary()}")

    payload = {
        "name": "session",
        "smoke": bool(args.smoke),
        "extended": bool(args.extended),
        "config": config.to_dict(),
        "headline": headline,
        "rows": rows,
    }
    if args.json:
        pathlib.Path(args.json).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {args.json}")
    exec_oracle = args.exec_reuse or args.exec_mode == "delta"
    if (args.smoke and args.plane == "numpy" and not args.fleet
            and not exec_oracle and not check_regression(headline)):
        # the committed baseline is a numpy-plane thread-service run without
        # the exec-identity oracle; other planes, the process fleet, and
        # exec-reuse/delta runs (which fully re-execute every pair for the
        # oracle) smoke for identity (the oracles above), not for this rate
        # guard — the fleet's own guard lives in service_bench /
        # BENCH_service.json, the delta tier's in delta_bench / BENCH_delta.json
        raise SystemExit(1)


if __name__ == "__main__":
    main()
