"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (scaffold contract) after each
section's human-readable output.  ``--json BENCH_<name>.json`` additionally
writes the summary as machine-readable JSON (one object per section:
``{"us_per_call": ..., "derived": ...}``) — the same format family as the
committed ``benchmarks/BENCH_search.json`` baseline the CI perf-smoke job
guards against.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time


def _csv(name: str, seconds: float, derived: str) -> str:
    return f"{name},{seconds * 1e6:.1f},{derived}"


def main() -> None:
    sys.path.insert(0, ".")
    ap = argparse.ArgumentParser(description="run all benchmark sections")
    ap.add_argument(
        "--json",
        metavar="BENCH_<name>.json",
        help="write the section summaries as JSON to this path",
    )
    args = ap.parse_args()
    from repro.engine.plane.jax_plane import use_compile_cache

    use_compile_cache(pathlib.Path(__file__).resolve().parent.parent)
    from benchmarks import (
        chain_bench,
        delta_bench,
        exec_bench,
        figs_scaling,
        plane_bench,
        search_bench,
        service_bench,
        session_bench,
        table1_ev_support,
        table5_comparison,
        table6_optimizations,
    )

    csv_lines = []

    print("== Table 1: direct EV support across workloads ==")
    t0 = time.perf_counter()
    rows = table1_ev_support.run()
    complex_rows = [r for r in rows if r["workload"].startswith("W")]
    pct = sum(r["pct_supported"] for r in complex_rows) / max(1, len(complex_rows))
    csv_lines.append(_csv("table1_ev_support", time.perf_counter() - t0,
                          f"complex_workloads_avg_supported={pct:.1f}%"))

    print("\n== Table 5: Veer vs Veer+ vs direct Spes ==")
    t0 = time.perf_counter()
    rows = table5_comparison.run()
    s = rows[-1]
    csv_lines.append(_csv(
        "table5_comparison", time.perf_counter() - t0,
        f"eq% spes={s['spes_pct_eq']:.0f} veer={s['veer_pct_eq']:.0f} "
        f"veer+={s['veer+_pct_eq']:.0f}; ineq% spes={s['spes_pct_ineq']:.0f} "
        f"veer={s['veer_pct_ineq']:.0f} veer+={s['veer+_pct_ineq']:.0f}",
    ))

    print("\n== Table 6: optimization ablation (W3, 3 edits) ==")
    t0 = time.perf_counter()
    rows = table6_optimizations.run()
    worst = max(rows, key=lambda r: r["decompositions"])
    best = min((r for r in rows if r["verdict"] is True), key=lambda r: r["total_s"],
               default=rows[0])
    csv_lines.append(_csv(
        "table6_optimizations", time.perf_counter() - t0,
        f"baseline_decomps={worst['decompositions']} best_decomps={best['decompositions']} "
        f"best_flags=S{int(best['S'])}P{int(best['P'])}R{int(best['R'])} "
        f"best_total={best['total_s']}s",
    ))

    print("\n== Figures 24-28: scaling experiments ==")
    t0 = time.perf_counter()
    rows = figs_scaling.run()
    f24 = [r for r in rows if r.get("fig") == "24"]
    speedups = [
        r["veer_decomps"] / max(1, r["veerplus_decomps"]) for r in f24
    ]
    csv_lines.append(_csv(
        "figs24_28_scaling", time.perf_counter() - t0,
        f"median_decomp_reduction={sorted(speedups)[len(speedups)//2]:.1f}x",
    ))

    print("\n== Chain verification: verdict cache + certificates ==")
    t0 = time.perf_counter()
    baseline, cached, warm = chain_bench.run(8)
    base_calls = sum(b["ev_calls"] for b in baseline)
    saved_pct = 100.0 * (1 - cached.total_ev_calls / max(1, base_calls))
    print(cached.summary())
    csv_lines.append(_csv(
        "chain_bench", time.perf_counter() - t0,
        f"ev_calls_saved={saved_pct:.0f}% warm_ev_calls={warm.total_ev_calls} "
        f"warm_cert_backed={100.0 * warm.certified_fraction:.0f}%",
    ))

    print("\n== Service throughput: 4 concurrent clients, shared cache ==")
    t0 = time.perf_counter()
    r = service_bench.run(clients=4, workers=4, n_versions=12)
    print(
        f"sequential {r['seq_pairs_per_sec']:.1f} pairs/s vs service "
        f"{r['svc_pairs_per_sec']:.1f} pairs/s ({r['speedup']:.1f}x), "
        f"EV calls {r['base_ev_calls']} -> {r['svc_ev_calls']} "
        f"({r['ev_calls_saved_pct']:.0f}% saved), "
        f"replay {r['replayed']}/{r['replayed'] + r['replay_failures']} ok, "
        f"{r['verdict_mismatches']} verdict mismatches"
    )
    fr = service_bench.run_fleet(clients=2, fleet=2, n_versions=6,
                                 shared_tier="remote")
    print(
        f"fleet 1 vs {fr['fleet']} processes (remote tier): "
        f"{fr['fleet_scaling']:.2f}x scaling, "
        f"{fr['verdict_mismatches']} mismatches, "
        f"{fr['replay_failures']} replay failures"
    )
    csv_lines.append(_csv(
        "service_bench", time.perf_counter() - t0,
        f"speedup={r['speedup']:.1f}x pairs_per_sec={r['svc_pairs_per_sec']:.0f} "
        f"ev_calls_saved={r['ev_calls_saved_pct']:.0f}% "
        f"replay_ok={r['replay_ok_pct']:.0f}% "
        f"fleet_scaling={fr['fleet_scaling']:.2f}x",
    ))

    print("\n== Edit-session stress: generated traffic + differential oracles ==")
    t0 = time.perf_counter()
    from repro.workload import WorkloadConfig

    _, h, _ = session_bench.run(
        WorkloadConfig(sessions=4, clients=4, chain_length=8,
                       max_decompositions=60),
        baseline=False,
    )
    csv_lines.append(_csv(
        "session_bench", time.perf_counter() - t0,
        f"pairs={h['pairs']} pairs_per_sec={h['pairs_per_sec']:.1f} "
        f"verified={100 * h['verified_fraction']:.0f}% "
        f"violations={h['violations']}",
    ))

    print("\n== Execute-with-reuse: chain time vs full re-execution ==")
    t0 = time.perf_counter()
    _, h = exec_bench.run(rows=exec_bench.SMOKE_ROWS, disk=False)
    csv_lines.append(_csv(
        "exec_bench", time.perf_counter() - t0,
        f"speedup={h['speedup']:.1f}x exec_fraction={h['exec_fraction'] * 100:.0f}% "
        f"tables_served={h['tables_served']}",
    ))

    print("\n== Delta-cone execution: row deltas vs cone recompute ==")
    t0 = time.perf_counter()
    _, h = delta_bench.run(rows=delta_bench.SMOKE_ROWS)
    csv_lines.append(_csv(
        "delta_bench", time.perf_counter() - t0,
        f"speedup={h['speedup']:.1f}x "
        f"delta_fraction={h['delta_fraction'] * 100:.1f}% "
        f"certified_pairs={h['certified_pairs']}",
    ))

    print("\n== Data plane: jax lowering vs reference engine ==")
    t0 = time.perf_counter()
    h = plane_bench.run_chain(plane_bench.SMOKE_ROWS)
    h.update(plane_bench.run_session())
    csv_lines.append(_csv(
        "plane_bench", time.perf_counter() - t0,
        f"speedup={h['speedup']:.1f}x jax_rows_per_sec={h['jax_rows_per_s']} "
        f"ops_lowered={h['ops_lowered']} "
        f"certs_replayed={h['certificates_replayed_ok']}",
    ))

    print("\n== Search kernel: bitmask vs reference decompositions/sec ==")
    t0 = time.perf_counter()
    _, headline = search_bench.run(
        sizes=search_bench.SMOKE_SIZES, budget=search_bench.SMOKE_BUDGET
    )
    csv_lines.append(_csv(
        "search_bench", time.perf_counter() - t0,
        f"decomps_per_sec={headline['bitmask_decomps_per_sec']:.0f} "
        f"speedup={headline['speedup']:.1f}x "
        f"@{headline['changes']}changes",
    ))

    print("\n== CSV summary ==")
    print("name,us_per_call,derived")
    for line in csv_lines:
        print(line)

    if args.json:
        sections = {}
        for line in csv_lines:
            name, us, derived = line.split(",", 2)
            sections[name] = {"us_per_call": float(us), "derived": derived}
        with open(args.json, "w") as f:
            json.dump({"name": "run", "sections": sections}, f, indent=2)
            f.write("\n")
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
