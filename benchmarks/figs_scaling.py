"""Paper Figs 24-28: multi-edit eq/ineq, edit distance, #changes, #operators."""

from __future__ import annotations

import time
from typing import Dict, List

from benchmarks.common import baseline_veer, plus_veer, timed_verify
from repro.core import dag as D
from repro.core.dag import Operator
from repro.workload.flows import (
    _B,
    _id_proj,
    apply_equivalent_edits,
    apply_inequivalent_edits,
    build_workloads,
    edits_with_distance,
)

BUDGET = 4000


def fig24_25_multi_edit(verbose: bool = True) -> List[Dict]:
    """Veer vs Veer⁺, 2 edits, equivalent + inequivalent pairs, W1-W8."""
    rows = []
    for name, P in build_workloads().items():
        for eq in (True, False):
            Q = (
                apply_equivalent_edits(P, 2, seed=5)
                if eq
                else apply_inequivalent_edits(
                    P, 2, seed=5,
                    kinds=["drop_proj_col"] if name >= "W5" else None,
                )
            )
            v1, s1, t1 = timed_verify(baseline_veer(BUDGET), P, Q)
            v2, s2, t2 = timed_verify(plus_veer(BUDGET), P, Q)
            rows.append(
                dict(
                    fig="24" if eq else "25",
                    workload=name, equivalent_pair=eq,
                    veer_verdict=v1, veer_decomps=s1.decompositions_explored, veer_s=round(t1, 3),
                    veerplus_verdict=v2, veerplus_decomps=s2.decompositions_explored,
                    veerplus_s=round(t2, 3),
                )
            )
            if verbose:
                r = rows[-1]
                print(
                    f"  fig{'24' if eq else '25'} {name}: veer {v1} {r['veer_decomps']}d {t1:.2f}s"
                    f" | veer+ {v2} {r['veerplus_decomps']}d {t2:.2f}s"
                )
    return rows


def fig26_distance(verbose: bool = True) -> List[Dict]:
    """Effect of the hop distance between two edits (W2)."""
    P = build_workloads()["W2"]
    rows = []
    for hops in (0, 1, 2, 3):
        try:
            Q = edits_with_distance(P, hops, seed=1)
        except ValueError:
            continue
        v1, s1, t1 = timed_verify(baseline_veer(BUDGET), P, Q)
        v2, s2, t2 = timed_verify(plus_veer(BUDGET), P, Q)
        rows.append(
            dict(
                fig="26", hops=hops,
                veer_verdict=v1, veer_decomps=s1.decompositions_explored, veer_s=round(t1, 3),
                veerplus_verdict=v2, veerplus_decomps=s2.decompositions_explored,
                veerplus_s=round(t2, 3),
            )
        )
        if verbose:
            r = rows[-1]
            print(f"  fig26 hops={hops}: veer {v1} {r['veer_decomps']}d {t1:.2f}s | "
                  f"veer+ {v2} {r['veerplus_decomps']}d {t2:.2f}s")
    return rows


def fig27_num_changes(verbose: bool = True) -> List[Dict]:
    """Effect of the number of changes (W1, 1-4 edits)."""
    P = build_workloads()["W1"]
    rows = []
    for n in (1, 2, 3, 4):
        Q = apply_equivalent_edits(P, n, seed=7, kinds=["empty_filter", "empty_project"])
        v1, s1, t1 = timed_verify(baseline_veer(BUDGET), P, Q)
        v2, s2, t2 = timed_verify(plus_veer(BUDGET), P, Q)
        rows.append(
            dict(
                fig="27", n_changes=n,
                veer_verdict=v1, veer_decomps=s1.decompositions_explored, veer_s=round(t1, 3),
                veerplus_verdict=v2, veerplus_decomps=s2.decompositions_explored,
                veerplus_s=round(t2, 3),
            )
        )
        if verbose:
            r = rows[-1]
            print(f"  fig27 n={n}: veer {v1} {r['veer_decomps']}d {t1:.2f}s | "
                  f"veer+ {v2} {r['veerplus_decomps']}d {t2:.2f}s")
    return rows


def fig28_num_operators(verbose: bool = True) -> List[Dict]:
    """Effect of workflow size: W2 padded with extra supported operators."""
    base = build_workloads()["W2"]
    rows = []
    for extra in (2, 3, 4, 5):
        P = apply_equivalent_edits(base, extra, seed=13, kinds=["empty_project"])
        Q = apply_equivalent_edits(P, 2, seed=5)
        v1, s1, t1 = timed_verify(baseline_veer(BUDGET), P, Q)
        v2, s2, t2 = timed_verify(plus_veer(BUDGET), P, Q)
        rows.append(
            dict(
                fig="28", n_ops=len(P.ops),
                veer_verdict=v1, veer_decomps=s1.decompositions_explored, veer_s=round(t1, 3),
                veerplus_verdict=v2, veerplus_decomps=s2.decompositions_explored,
                veerplus_s=round(t2, 3),
            )
        )
        if verbose:
            r = rows[-1]
            print(f"  fig28 ops={r['n_ops']}: veer {v1} {r['veer_decomps']}d {t1:.2f}s | "
                  f"veer+ {v2} {r['veerplus_decomps']}d {t2:.2f}s")
    return rows


def run(verbose: bool = True) -> List[Dict]:
    rows = []
    rows += fig24_25_multi_edit(verbose)
    rows += fig26_distance(verbose)
    rows += fig27_num_changes(verbose)
    rows += fig28_num_operators(verbose)
    return rows


if __name__ == "__main__":
    run()
