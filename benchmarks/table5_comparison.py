"""Paper Table 5: Veer vs Veer⁺ vs direct-Spes on W1-W8 (eq + ineq pairs)."""

from __future__ import annotations

import time
from typing import Dict, List

from benchmarks.common import baseline_veer, plus_veer, spes_direct, timed_verify
from repro.workload.flows import (
    apply_equivalent_edits,
    apply_inequivalent_edits,
    build_workloads,
)

BUDGET = 4000  # decomposition cap standing in for the paper's 1h timeout


def run(verbose: bool = True) -> List[Dict]:
    rows = []
    agg = {
        "spes": dict(eq=0, ineq=0, t_eq=0.0, t_ineq=0.0),
        "veer": dict(eq=0, ineq=0, t_eq=0.0, t_ineq=0.0),
        "veer+": dict(eq=0, ineq=0, t_eq=0.0, t_ineq=0.0),
    }
    workloads = build_workloads()
    for name, P in workloads.items():
        Qe = apply_equivalent_edits(P, 2, seed=5)
        ineq_kinds = (
            ["drop_proj_col"] if name in ("W5", "W6", "W7", "W8") else ["bump_const", "new_filter"]
        )
        Qi = apply_inequivalent_edits(P, 2, seed=5, kinds=ineq_kinds)

        t0 = time.perf_counter()
        sd_eq = spes_direct(P, Qe)
        t_sd_eq = time.perf_counter() - t0
        t0 = time.perf_counter()
        sd_ineq = spes_direct(P, Qi)
        t_sd_ineq = time.perf_counter() - t0

        veer = baseline_veer(BUDGET)
        plus = plus_veer(BUDGET)
        v_eq, s_eq, t_eq = timed_verify(veer, P, Qe)
        p_eq, ps_eq, pt_eq = timed_verify(plus, P, Qe)
        v_iq, s_iq, t_iq = timed_verify(veer, P, Qi)
        p_iq, ps_iq, pt_iq = timed_verify(plus, P, Qi)

        agg["spes"]["eq"] += sd_eq is True
        agg["spes"]["ineq"] += sd_ineq is False
        agg["veer"]["eq"] += v_eq is True
        agg["veer"]["ineq"] += v_iq is False
        agg["veer+"]["eq"] += p_eq is True
        agg["veer+"]["ineq"] += p_iq is False
        for k, t_e, t_i in (("spes", t_sd_eq, t_sd_ineq), ("veer", t_eq, t_iq), ("veer+", pt_eq, pt_iq)):
            agg[k]["t_eq"] += t_e
            agg[k]["t_ineq"] += t_i

        rows.append(
            dict(
                workload=name,
                spes_eq=sd_eq, veer_eq=v_eq, veerplus_eq=p_eq,
                spes_ineq=sd_ineq, veer_ineq=v_iq, veerplus_ineq=p_iq,
                veer_eq_s=round(t_eq, 3), veerplus_eq_s=round(pt_eq, 3),
                veer_ineq_s=round(t_iq, 3), veerplus_ineq_s=round(pt_iq, 3),
                veer_decomps=s_eq.decompositions_explored,
                veerplus_decomps=ps_eq.decompositions_explored,
            )
        )
        if verbose:
            print(
                f"  {name}: eq spes={sd_eq} veer={v_eq}({t_eq:.2f}s) veer+={p_eq}({pt_eq:.2f}s) | "
                f"ineq spes={sd_ineq} veer={v_iq}({t_iq:.2f}s) veer+={p_iq}({pt_iq:.2f}s)"
            )
    n = len(workloads)
    summary = dict(workload="SUMMARY")
    for k in agg:
        summary[f"{k}_pct_eq"] = 100.0 * agg[k]["eq"] / n
        summary[f"{k}_pct_ineq"] = 100.0 * agg[k]["ineq"] / n
        summary[f"{k}_avg_eq_s"] = agg[k]["t_eq"] / n
        summary[f"{k}_avg_ineq_s"] = agg[k]["t_ineq"] / n
    rows.append(summary)
    if verbose:
        print(
            "  SUMMARY: proved-eq%: "
            + " ".join(f"{k}={summary[f'{k}_pct_eq']:.0f}%" for k in agg)
            + " | proved-ineq%: "
            + " ".join(f"{k}={summary[f'{k}_pct_ineq']:.0f}%" for k in agg)
        )
    return rows


if __name__ == "__main__":
    run()
