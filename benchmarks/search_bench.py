"""Search-kernel benchmark: decompositions/sec, bitmask kernel vs reference.

The verdict caches from earlier PRs removed ~95% of EV calls, which leaves
Algorithm 2's decomposition search itself as Veer's cost on pairs with many
changes (the frontier is exponential in the change count).  This benchmark
isolates that cost: synthetic version pairs scale from 4 to 14 changes on a
large workload (W4, 28 ops), the shared ``VerdictCache`` is fully warmed
first (verdicts *and* validity — zero EV calls during measurement), and then
the same budgeted search runs once per backend:

  * ``reference`` — the retained pre-kernel frozenset search
    (``repro.core.search_ref``);
  * ``bitmask``   — the interned-integer-window kernel (the default).

Both backends explore the identical decomposition sequence, so
decompositions/sec is an apples-to-apples throughput number; the benchmark
additionally *asserts* per size that verdicts, explored counts and
certificate JSON are byte-identical across backends.

Usage (from the repo root):

    python benchmarks/search_bench.py                 # full sweep, 4..14 changes
    python benchmarks/search_bench.py --smoke         # CI mode: small sweep +
                                                      #   >30% regression guard
                                                      #   vs BENCH_search.json
    python benchmarks/search_bench.py --json OUT.json # write machine-readable
                                                      #   results (the committed
                                                      #   baseline is
                                                      #   benchmarks/BENCH_search.json)
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

from repro.api import default_registry  # noqa: E402
from repro.api.certificate import certificate_from_evidence  # noqa: E402
from repro.core.ev.cache import VerdictCache  # noqa: E402
from repro.core.verifier import Veer  # noqa: E402
from repro.workload.flows import apply_equivalent_edits, build_workloads  # noqa: E402

BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "BENCH_search.json"
GUIDED_BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "BENCH_guided.json"
# the acceptance headline is measured at this change count (ISSUE 4)
HEADLINE_CHANGES = 12
# CI guard: fail when decompositions/sec drops more than this vs the baseline
REGRESSION_TOLERANCE = 0.30
# --guided acceptance floor: guided decompositions-to-first-certificate must
# beat both unguided modes by at least this factor on the workload mix
GUIDED_SPEEDUP_FLOOR = 5.0

FULL_SIZES = (4, 6, 8, 10, 12, 14)
FULL_BUDGET = 6_000
SMOKE_SIZES = (4, 8, 12)
# large enough that the 12-change smoke row sits in the same search-dominated
# regime as the full sweep (the per-unique-window costs amortize away and the
# measured speedup matches the full-budget headline)
SMOKE_BUDGET = 3_000


def _make_pair(n_changes: int, workload: str = "W4", seed: int = 0):
    P = build_workloads()[workload]
    Q = apply_equivalent_edits(P, n_changes, seed=seed)
    return P, Q


def _veer(backend: str, budget: int, cache: VerdictCache) -> Veer:
    # the paper's unoptimized Veer: no ranking/eager shortcuts, so the
    # search explores the frontier instead of concluding after a handful of
    # decompositions — the regime where kernel throughput matters
    return Veer(
        default_registry().build(),
        search_backend=backend,
        max_decompositions=budget,
        verdict_cache=cache,
    )


def _measure(backend: str, P, Q, budget: int, cache: VerdictCache, reps: int = 1):
    """Best-of-``reps`` wall time (each rep is a fresh verifier over the same
    warm cache, so every rep explores the identical decomposition sequence —
    best-of-N strips scheduler noise without changing what is measured)."""
    wall = None
    for _ in range(max(1, reps)):
        veer = _veer(backend, budget, cache)
        t0 = time.perf_counter()
        verdict, stats, evidence = veer.verify_with_evidence(P, Q)
        rep_wall = time.perf_counter() - t0
        wall = rep_wall if wall is None else min(wall, rep_wall)
    cert = certificate_from_evidence(evidence)
    return {
        "verdict": verdict,
        "decompositions": stats.decompositions_explored,
        "pushes_skipped": stats.pushes_skipped,
        "ev_calls": stats.ev_calls,
        "wall_s": wall,
        "decomps_per_sec": stats.decompositions_explored / max(wall, 1e-9),
        "cert_json": cert.to_json() if cert is not None else None,
    }


def run(sizes=FULL_SIZES, budget: int = FULL_BUDGET, workload: str = "W4"):
    """Returns ``(rows, headline)``; raises SystemExit on any cross-backend
    verdict/exploration/certificate mismatch (the kernel must be a pure
    performance change)."""
    rows = []
    for n in sizes:
        P, Q = _make_pair(n, workload)
        cache = VerdictCache()
        # warm verdicts AND validity so measurement needs zero EV work
        warm = _measure("bitmask", P, Q, budget, cache)
        ref = _measure("reference", P, Q, budget, cache, reps=2)
        bit = _measure("bitmask", P, Q, budget, cache, reps=2)
        for field in ("verdict", "decompositions", "pushes_skipped", "cert_json"):
            if ref[field] != bit[field]:
                raise SystemExit(
                    f"backend mismatch at {n} changes: {field} "
                    f"ref={ref[field]!r} bitmask={bit[field]!r}"
                )
        if bit["ev_calls"]:
            raise SystemExit(
                f"cache-warm run made {bit['ev_calls']} EV calls at {n} changes"
            )
        rows.append(
            {
                "changes": n,
                "workload": workload,
                "budget": budget,
                "verdict": {True: "EQ", False: "NEQ", None: "UNK"}[bit["verdict"]],
                "decompositions": bit["decompositions"],
                "warm_ev_calls": warm["ev_calls"],
                "ref_decomps_per_sec": ref["decomps_per_sec"],
                "bitmask_decomps_per_sec": bit["decomps_per_sec"],
                "speedup": bit["decomps_per_sec"] / max(ref["decomps_per_sec"], 1e-9),
                "certified": bit["cert_json"] is not None,
            }
        )
        print(
            f"{workload} changes={n:>2} decomps={bit['decompositions']:>6} "
            f"ref={ref['decomps_per_sec']:>9,.0f}/s "
            f"bitmask={bit['decomps_per_sec']:>9,.0f}/s "
            f"speedup={rows[-1]['speedup']:.1f}x verdict={rows[-1]['verdict']}"
        )
    headline_rows = [r for r in rows if r["changes"] == HEADLINE_CHANGES] or rows[-1:]
    h = headline_rows[0]
    headline = {
        "changes": h["changes"],
        "workload": h["workload"],
        "budget": h["budget"],
        "bitmask_decomps_per_sec": h["bitmask_decomps_per_sec"],
        "ref_decomps_per_sec": h["ref_decomps_per_sec"],
        "speedup": h["speedup"],
    }
    print(
        f"headline ({h['changes']} changes, cache-warm): "
        f"{h['bitmask_decomps_per_sec']:,.0f} decomps/s, "
        f"{h['speedup']:.1f}x vs reference"
    )
    return rows, headline


# ---------------------------------------------------------------------------
# --guided: learned guidance vs the unguided search (docs/SEARCH_GUIDANCE.md)
# ---------------------------------------------------------------------------

# the three search policies the guided benchmark races head-to-head:
#   blind   — the paper's unoptimized Algorithm 2 (the committed
#             BENCH_search rows: budget-exhausted UNK on every smoke size)
#   ranking — §7.3 coverage ranking, the best unguided policy
#   guided  — the learned scorer on top of ranking (tie-break), with eager
#             verification of nominated decompositions
GUIDED_MODES = ("blind", "ranking", "guided")


def _policy_veer(mode: str, backend: str, budget: int, cache, guidance):
    kw = {}
    if mode == "ranking":
        kw = dict(ranking=True)
    elif mode == "guided":
        kw = dict(ranking=True, eager_verify=True, guidance=guidance)
    return Veer(
        default_registry().build(),
        search_backend=backend,
        max_decompositions=budget,
        verdict_cache=cache,
        **kw,
    )


def _measure_policy(mode: str, backend: str, P, Q, budget: int, guidance):
    """One cold-cache run: every policy pays its own EV calls, so wall time
    and ``ev_calls`` are honest per-policy costs, and the deterministic
    ``decompositions_to_first_certificate`` is the machine-independent
    headline metric."""
    veer = _policy_veer(mode, backend, budget, VerdictCache(), guidance)
    t0 = time.perf_counter()
    verdict, stats, evidence = veer.verify_with_evidence(P, Q)
    wall = time.perf_counter() - t0
    cert = certificate_from_evidence(evidence)
    return {
        "verdict": {True: "EQ", False: "NEQ", None: "UNK"}[verdict],
        "first_certificate": stats.decompositions_to_first_certificate,
        "decompositions": stats.decompositions_explored,
        "ev_calls": stats.ev_calls,
        "ev_attempts": dict(sorted(stats.ev_attempts.items())),
        "wall_s": wall,
        "cert_json": cert.to_json() if cert is not None else None,
    }


def _geomean(xs):
    if not xs:
        return 0.0
    prod = 1.0
    for x in xs:
        prod *= x
    return prod ** (1.0 / len(xs))


def run_guided(
    sizes=SMOKE_SIZES,
    budget: int = SMOKE_BUDGET,
    workload: str = "W4",
    guidance_path=None,
):
    """Race blind / ranking / guided per size; returns ``(rows, headline)``.

    Hard in-run audits (SystemExit on failure — the benchmark doubles as a
    soundness check):

      * guided bitmask and reference backends agree byte-for-byte on
        verdict, exploration count, first-certificate index and certificate
        JSON;
      * every pair of policies that both decide agrees on the verdict
        (guidance schedules work; it cannot flip an answer);
      * every guided certificate replays green against the version pair.
    """
    from repro.api.certificate import Certificate
    from repro.learn import load_guidance

    guidance = load_guidance(guidance_path)
    rows = []
    for n in sizes:
        P, Q = _make_pair(n, workload)
        row = {"changes": n, "workload": workload, "budget": budget}
        for mode in GUIDED_MODES:
            row[mode] = _measure_policy(mode, "bitmask", P, Q, budget, guidance)
        # audit 1: the guided exploration is backend-invariant
        ref = _measure_policy("guided", "reference", P, Q, budget, guidance)
        for field in ("verdict", "first_certificate", "decompositions",
                      "ev_attempts", "cert_json"):
            if ref[field] != row["guided"][field]:
                raise SystemExit(
                    f"guided backend mismatch at {n} changes: {field} "
                    f"bitmask={row['guided'][field]!r} reference={ref[field]!r}"
                )
        # audit 2: decided policies agree on the verdict
        decided = {
            m: row[m]["verdict"] for m in GUIDED_MODES
            if row[m]["verdict"] != "UNK"
        }
        if len(set(decided.values())) > 1:
            raise SystemExit(f"policy verdict disagreement at {n} changes: {decided}")
        # audit 3: the guided certificate replays green, bound to the pair
        if row["guided"]["cert_json"] is not None:
            report = Certificate.from_json(row["guided"]["cert_json"]).replay(
                P=P, Q=Q
            )
            if not report.ok:
                raise SystemExit(
                    f"guided certificate replay failed at {n} changes: "
                    f"{report.summary()}"
                )
        # speedups in decompositions-to-first-certificate; an undecided
        # policy is scored at the full budget (a lower bound on its true
        # cost, flagged so readers know the ratio is conservative)
        g_first = row["guided"]["first_certificate"]
        for mode in ("blind", "ranking"):
            first = row[mode]["first_certificate"]
            row[f"speedup_vs_{mode}"] = (
                (first or budget) / g_first if g_first else 0.0
            )
            row[f"speedup_vs_{mode}_is_lower_bound"] = first is None
        for m in GUIDED_MODES:
            del row[m]["cert_json"]  # audited above; too bulky to commit
        rows.append(row)
        print(
            f"{workload} changes={n:>2} "
            + " ".join(
                f"{m}={row[m]['verdict']}@"
                f"{row[m]['first_certificate'] or row[m]['decompositions']}"
                for m in GUIDED_MODES
            )
            + f" speedup_vs_blind={row['speedup_vs_blind']:.0f}x"
            + f" speedup_vs_ranking={row['speedup_vs_ranking']:.1f}x"
        )

    h_rows = [r for r in rows if r["changes"] == HEADLINE_CHANGES] or rows[-1:]
    h = h_rows[0]
    headline = {
        "changes": h["changes"],
        "workload": workload,
        "budget": budget,
        "guided_first_certificate": h["guided"]["first_certificate"],
        "ranking_first_certificate": h["ranking"]["first_certificate"],
        "blind_first_certificate": h["blind"]["first_certificate"],
        # rows the unguided baseline left budget-exhausted-UNK that guidance
        # certified within the same budget (the ISSUE 9 acceptance flip)
        "unk_to_eq": sum(
            1 for r in rows
            if r["blind"]["verdict"] == "UNK" and r["guided"]["verdict"] == "EQ"
        ),
        "mix_speedup_vs_blind": _geomean(
            [r["speedup_vs_blind"] for r in rows if r["speedup_vs_blind"] > 0]
            if all(r["speedup_vs_blind"] > 0 for r in rows) else []
        ),
        "mix_speedup_vs_ranking": _geomean(
            [r["speedup_vs_ranking"] for r in rows if r["speedup_vs_ranking"] > 0]
            if all(r["speedup_vs_ranking"] > 0 for r in rows) else []
        ),
    }
    print(
        f"guided headline ({h['changes']} changes): first certificate at "
        f"{headline['guided_first_certificate']} decompositions "
        f"(ranking: {headline['ranking_first_certificate'] or 'UNK@budget'}, "
        f"blind: {headline['blind_first_certificate'] or 'UNK@budget'}); "
        f"mix speedup {headline['mix_speedup_vs_blind']:.0f}x vs blind, "
        f"{headline['mix_speedup_vs_ranking']:.1f}x vs ranking; "
        f"{headline['unk_to_eq']} UNK row(s) flipped to certified EQ"
    )
    return rows, headline


def check_guided_regression(
    headline, baseline_path: pathlib.Path = GUIDED_BASELINE_PATH
) -> bool:
    """CI guard for --guided --smoke.

    Two machine-independent checks (decomposition counts are deterministic,
    so no wall-clock tolerance games):

      1. floors that must hold outright: every blind-UNK row still flips to
         certified EQ, and the mix speedup vs blind stays ≥ the acceptance
         floor (5x);
      2. the headline guided first-certificate index must not drift worse
         than the committed baseline by more than REGRESSION_TOLERANCE —
         with a speedup-ratio fallback: a retrained artifact that moves the
         absolute index but keeps the in-run mix speedup vs ranking within
         tolerance of the committed one is accepted (the artifact changed,
         the search did not regress).
    """
    ok = True
    if headline["unk_to_eq"] < 1:
        print("FAIL: no budget-exhausted-UNK row was flipped to certified EQ")
        ok = False
    if headline["mix_speedup_vs_blind"] < GUIDED_SPEEDUP_FLOOR:
        print(
            f"FAIL: mix speedup vs blind "
            f"{headline['mix_speedup_vs_blind']:.1f}x is below the "
            f"{GUIDED_SPEEDUP_FLOOR:.0f}x acceptance floor"
        )
        ok = False
    if not baseline_path.exists():
        print(f"no committed guided baseline at {baseline_path}; floors only")
        return ok
    baseline = json.loads(baseline_path.read_text())["headline"]
    base_first = baseline["guided_first_certificate"]
    first = headline["guided_first_certificate"]
    ceiling = base_first * (1.0 + REGRESSION_TOLERANCE)
    print(
        f"guided regression guard: first certificate at {first} vs committed "
        f"{base_first} (ceiling {ceiling:.0f})"
    )
    if first is None or first > ceiling:
        ratio_floor = (
            baseline["mix_speedup_vs_ranking"] * (1.0 - REGRESSION_TOLERANCE)
        )
        print(
            f"  above ceiling; checking speedup-ratio fallback: "
            f"{headline['mix_speedup_vs_ranking']:.1f}x vs ranking "
            f"(committed {baseline['mix_speedup_vs_ranking']:.1f}x, "
            f"floor {ratio_floor:.1f}x)"
        )
        if first is None or headline["mix_speedup_vs_ranking"] < ratio_floor:
            print(
                "FAIL: guided first-certificate index AND mix speedup vs "
                f"ranking both regressed >{REGRESSION_TOLERANCE:.0%} vs the "
                "committed baseline"
            )
            ok = False
        else:
            print("  speedup held — artifact drift, not a search regression")
    return ok


def check_regression(headline, baseline_path: pathlib.Path = BASELINE_PATH) -> bool:
    """CI guard: compare the smoke headline against the committed baseline;
    True = OK, False = regressed more than ``REGRESSION_TOLERANCE``."""
    if not baseline_path.exists():
        print(f"no committed baseline at {baseline_path}; skipping guard")
        return True
    baseline = json.loads(baseline_path.read_text())["headline"]
    floor = baseline["bitmask_decomps_per_sec"] * (1.0 - REGRESSION_TOLERANCE)
    rate = headline["bitmask_decomps_per_sec"]
    print(
        f"regression guard: {rate:,.0f} decomps/s vs committed "
        f"{baseline['bitmask_decomps_per_sec']:,.0f} (floor {floor:,.0f})"
    )
    if rate >= floor:
        return True
    # absolute decomps/sec depends on runner hardware; the in-run speedup vs
    # the reference backend (measured on the SAME machine, same run) does
    # not — accept when the ratio held, so a slow CI runner doesn't read as
    # a code regression and a fast one doesn't mask a real one
    speedup_floor = baseline["speedup"] * (1.0 - REGRESSION_TOLERANCE)
    print(
        f"  below absolute floor; checking machine-independent speedup: "
        f"{headline['speedup']:.2f}x vs committed {baseline['speedup']:.2f}x "
        f"(floor {speedup_floor:.2f}x)"
    )
    if headline["speedup"] >= speedup_floor:
        print("  speedup held — slower runner, not a kernel regression")
        return True
    print(
        f"FAIL: bitmask decompositions/sec AND kernel speedup both regressed "
        f">{REGRESSION_TOLERANCE:.0%} vs the committed baseline"
    )
    return False


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep + regression guard vs BENCH_search.json")
    ap.add_argument("--json", metavar="PATH",
                    help="write rows + headline as JSON (BENCH_<name>.json style)")
    ap.add_argument("--budget", type=int, default=None,
                    help="override the decomposition budget")
    ap.add_argument("--workload", default="W4", help="base workload (default W4)")
    ap.add_argument("--guided", action="store_true",
                    help="race blind/ranking/guided policies on "
                         "decompositions-to-first-certificate "
                         "(baseline: BENCH_guided.json)")
    ap.add_argument("--guidance-path", metavar="JSON", default=None,
                    help="guidance artifact for --guided (default: the "
                         "committed pretrained.json)")
    args = ap.parse_args()

    sizes = SMOKE_SIZES if args.smoke else FULL_SIZES
    budget = args.budget or (SMOKE_BUDGET if args.smoke else FULL_BUDGET)
    if args.guided:
        # the guided race always runs the smoke budget unless overridden:
        # its committed baseline rows are the BENCH_search smoke rows' regime
        budget = args.budget or SMOKE_BUDGET
        rows, headline = run_guided(
            sizes=sizes, budget=budget, workload=args.workload,
            guidance_path=args.guidance_path,
        )
        payload = {
            "name": "guided",
            "smoke": bool(args.smoke),
            "headline": headline,
            "rows": rows,
        }
        if args.json:
            pathlib.Path(args.json).write_text(
                json.dumps(payload, indent=2) + "\n"
            )
            print(f"wrote {args.json}")
        if args.smoke and not check_guided_regression(headline):
            raise SystemExit(1)
        return

    rows, headline = run(sizes=sizes, budget=budget, workload=args.workload)

    payload = {
        "name": "search",
        "smoke": bool(args.smoke),
        "headline": headline,
        "rows": rows,
    }
    if args.json:
        pathlib.Path(args.json).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {args.json}")
    if args.smoke and not check_regression(headline):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
