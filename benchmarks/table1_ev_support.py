"""Paper Table 1: % of version pairs the published EVs can verify DIRECTLY
(whole pair pushed to the EV, no Veer windows).

Workloads: a Calcite-like pure-SPJ(+agg) set (EVs partially work) and the
W1-W8 complex workflows (EVs fail — unsupported operators everywhere).
"""

from __future__ import annotations

import time
from typing import Dict, List

from repro.api import default_registry
from repro.core import dag as D
from repro.core.dag import DataflowDAG
from repro.core.edits import identity_mapping
from repro.core.window import VersionPair
from repro.workload.flows import apply_equivalent_edits, build_workloads, _B, _id_proj


def _calcite_like() -> Dict[str, DataflowDAG]:
    """Small SPJ/SPJA queries in the EVs' fragment."""
    out = {}
    b = _B()
    s = b.src("t", ["a", "b", "c"])
    f1 = b.filt("f1", s, "a", ">", 2)
    p = b.proj("p", f1, _id_proj(["a", "b", "c"]))
    b.sink("sink", p)
    out["calcite_spj"] = b.build()

    b = _B()
    l = b.src("l", ["a", "b"])
    r = b.src("r", ["k", "v"])
    j = b.join("j", l, r, [("a", "k")])
    f = b.filt("f", j, "v", "<", 5)
    b.sink("sink", f)
    out["calcite_join"] = b.build()

    b = _B()
    s = b.src("t", ["a", "b", "c"])
    a = b.agg("g", s, ["a"], [("sum", "b", "s")])
    f = b.filt("f", a, "a", ">", 1)
    b.sink("sink", f)
    out["calcite_agg"] = b.build()
    return out


def run(verbose: bool = True) -> List[Dict]:
    evs = default_registry().build()
    workloads = {**_calcite_like(), **build_workloads()}
    rows = []
    for name, P in workloads.items():
        Q = apply_equivalent_edits(P, 1, seed=11, kinds=["empty_filter"])
        pair = VersionPair(P, Q, identity_mapping(P, Q))
        qp = pair.to_query_pair(frozenset(range(len(pair.units))))
        t0 = time.perf_counter()
        support = {}
        for ev in evs:
            ok = qp is not None and qp.semantics in ev.semantics and ev.validate(qp)
            verdict = ev.check(qp) if ok else None
            support[ev.name] = bool(ok and verdict is True)
        dt = time.perf_counter() - t0
        rows.append(
            dict(
                workload=name,
                n_ops=len(P.ops),
                us_per_call=dt * 1e6 / len(evs),
                **{f"ev_{k}": v for k, v in support.items()},
                pct_supported=100.0 * sum(support.values()) / len(evs),
            )
        )
        if verbose:
            print(f"  {name:14s} ops={len(P.ops):3d} supported: "
                  + " ".join(f"{k}={'Y' if v else 'n'}" for k, v in support.items()))
    return rows


if __name__ == "__main__":
    run()
