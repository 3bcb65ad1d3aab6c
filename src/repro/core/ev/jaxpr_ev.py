"""JaxprEV — JAX-native equivalence verifier (beyond-paper, DESIGN.md §2).

Lowers each window sub-DAG to a jaxpr over symbolic ``(cols, mask)`` tables
and compares the canonicalized jaxprs.  Sound: identical jaxprs with aligned
inputs denote identical computations, and every registered body is a faithful
model of the engine op (all semantics-bearing properties are folded into the
trace).  Incomplete (syntactic), never proves inequivalence.

This is the framework's answer to the paper's W8 failure mode: "the change
was made on a UDF operator, resulting in the absence of a valid window" —
here a UDF whose body is a registered JAX function *is* verifiable, e.g.
windows where a UDF moved past a commuting filter, or where the UDF is
unchanged and only surrounding SPJ ops were rewritten into an identical
pipeline.

Restrictions: every operator traceable (see ``jax_bodies.TRACEABLE_OPS``),
numeric predicates only.  Restriction-monotonic: adding an untraceable op to
any window keeps it invalid.

Supported fragment (format shared by all EVs; see docs/ARCHITECTURE.md):

    ============== ==========================================================
    EV             JaxprEV (``jaxpr``)
    Operators      every op with a registered JAX body
                   (``jax_bodies.TRACEABLE_OPS`` — relational core + UDF /
                   Classifier / DictionaryMatcher / Sentiment with numeric
                   models)
    Semantics      set, bag, ordered
    Restrictions   J1 all operators have registered JAX bodies; J2 numeric
                   columns / predicates only
    Monotonic      yes
    Proves inequiv no — syntactic jaxpr comparison, True or Unknown only
    ============== ==========================================================
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dag as D
from repro.core.dag import BAG, ORDERED, SET, DataflowDAG, infer_schema
from repro.core.ev import jax_bodies as B
from repro.core.ev.base import BaseEV, QueryPair, Restriction

_SYMBOLIC_ROWS = 8  # capacity of symbolic tables; bodies are shape-generic


class JaxprEV(BaseEV):
    name = "jaxpr"
    semantics = frozenset({SET, BAG, ORDERED})
    restriction_monotonic = True
    can_prove_inequivalence = False
    supported_op_types = B.TRACEABLE_OPS

    def restrictions(self) -> List[Restriction]:
        return [
            Restriction("J1", "all operators have registered JAX bodies"),
            Restriction("J2", "numeric columns / predicates only"),
        ]

    def failed_restrictions(self, qp: QueryPair) -> List[str]:
        failed = []
        for dag in (qp.P, qp.Q):
            for op in dag.ops.values():
                if op.op_type not in B.TRACEABLE_OPS:
                    failed.append("J1")
                elif not B.op_traceable(op):
                    failed.append("J2")
        return sorted(set(failed))

    def validate(self, qp: QueryPair) -> bool:
        return not self.failed_restrictions(qp)

    def check(self, qp: QueryPair) -> Optional[bool]:
        try:
            # result tables carry column names: sink schemas must agree too
            sp = infer_schema(qp.P, {})
            sq = infer_schema(qp.Q, {})
            for p, q in qp.sink_pairs:
                if sp[p] != sq[q]:
                    return None
            ja = _window_jaxpr(qp.P, [p for p, _ in qp.sink_pairs])
            jb = _window_jaxpr(qp.Q, [q for _, q in qp.sink_pairs])
        except (B.TraceUnsupported, KeyError, TypeError, D.DAGError):
            return None
        return True if ja == jb else None


def _window_jaxpr(dag: DataflowDAG, sinks: List[str]) -> str:
    """Canonical jaxpr string of the sub-DAG as fn(source tables)->sink tables.

    Inputs are ordered by source id (shared between P and Q by construction
    of the QueryPair), outputs by the sink order given; each output is the
    sink's columns in schema order plus its mask — so column naming is
    erased and only computation structure remains.
    """
    src_ids = sorted(dag.sources)
    schemas = infer_schema(dag, {})

    def fn(*arrays):
        # unpack: one (cols..., n) group per source
        tables: Dict[str, B.JTable] = {}
        k = 0
        for sid in src_ids:
            sch = schemas[sid]
            cols = {c: arrays[k + i] for i, c in enumerate(sch)}
            mask = arrays[k + len(sch)]
            tables[sid] = (cols, mask)
            k += len(sch) + 1
        results: Dict[str, B.JTable] = {}
        for op_id in dag.topo_order():
            op = dag.ops[op_id]
            if op.op_type == D.SOURCE:
                results[op_id] = tables[op_id]
                continue
            ins = [results[l.src] for l in dag.in_links[op_id]]
            results[op_id] = B.execute_op_jax(op, ins)
        out = []
        for s in sinks:
            cols, mask = results[s]
            for c in schemas[s]:
                out.append(cols[c])
            out.append(mask)
        return tuple(out)

    # abstract avals: tracing never allocates, so verifying a window never
    # initializes a device backend (the chip belongs to one process)
    col = jax.ShapeDtypeStruct((_SYMBOLIC_ROWS,), jnp.float32)
    mask = jax.ShapeDtypeStruct((_SYMBOLIC_ROWS,), jnp.bool_)
    avals = []
    for sid in src_ids:
        avals.extend([col] * len(schemas[sid]))
        avals.append(mask)
    jaxpr = jax.make_jaxpr(fn)(*avals)
    return str(jaxpr)
