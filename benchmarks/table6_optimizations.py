"""Paper Table 6: ablation of Veer⁺ optimizations (S/P/R) on W3 + 3 edits."""

from __future__ import annotations

import itertools
import time
from typing import Dict, List

from benchmarks.common import timed_verify
from repro.api import default_registry


def PAPER_SET():
    # paper-faithful EV set: without JaxprEV the Sort stays a segmentation
    # boundary (JaxprEV supports Sort and would dissolve the segments)
    return default_registry().build(["equitas", "spes", "udp"])


from repro.core.verifier import Veer
from repro.workload.flows import apply_equivalent_edits, build_workloads

BUDGET = 25_000


def _w3_three_edits():
    """Paper setup: one edit after the (EV-unsupported) Sort, two before —
    so segmentation splits the decomposition space at the Sort boundary."""
    from repro.workload.flows import _splice, op, _schema_at, _id_proj
    from repro.core import dag as D
    from repro.core.dag import Link

    P = build_workloads()["W3"]
    # two edits before the sort (seed 4 spreads them across branches)
    Q = apply_equivalent_edits(P, 2, seed=4, kinds=["empty_filter", "empty_project"])
    # one empty-project edit after the sort
    l = [x for x in Q.links if x.src == "sort_amt"][0]
    sch = _schema_at(Q, "sort_amt")
    Q = _splice(Q, l, op("ep_after_sort", D.PROJECT, cols=_id_proj(sch)))
    return P, Q


def run(verbose: bool = True) -> List[Dict]:
    P, Q = _w3_three_edits()
    rows = []
    for seg, prune, rank in itertools.product([False, True], repeat=3):
        veer = Veer(
            PAPER_SET(),
            segmentation=seg,
            pruning=prune,
            ranking=rank,
            max_decompositions=BUDGET,
        )
        v, stats, dt = timed_verify(veer, P, Q)
        rows.append(
            dict(
                S=seg, P=prune, R=rank,
                verdict=v,
                decompositions=stats.decompositions_explored,
                explore_s=round(stats.explore_time, 3),
                ev_s=round(stats.ev_time, 3),
                ev_calls=stats.ev_calls,
                total_s=round(dt, 3),
                budget_exhausted=stats.budget_exhausted,
            )
        )
        if verbose:
            r = rows[-1]
            print(
                f"  S={int(seg)} P={int(prune)} R={int(rank)}: verdict={v} "
                f"decomps={r['decompositions']:6d} explore={r['explore_s']:7.3f}s "
                f"ev={r['ev_s']:6.3f}s total={r['total_s']:7.3f}s"
            )
    return rows


if __name__ == "__main__":
    run()
