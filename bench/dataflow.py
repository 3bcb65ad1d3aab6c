"""Plain dataflows, the seeded edit families, and the bridge to the program.

A configuration states its base dataflow as data (``dataflow`` in
``bench/configs/<name>.json``).  ``Flow`` holds it in a representation of
this package's own, so that the plain reference (``bench/reference.py``)
reads nothing the program built: ``to_dag`` is the only place a flow
becomes a ``repro.core.dag.DataflowDAG``.

The edit families follow the repository's session generator
(``repro.workload.generator``) and its Calcite / TPC-DS-iterative edits
(``benchmarks/workloads.py``), kept here so that no later change to those
files moves the benchmark's traffic.  Two departures, both so that edits
mean something on TPC-DS data rather than on the generator's 0..6 integer
domain: a new filter's constant and a narrowing/widening bound are drawn
from the column's domain as stated in the configuration, and always at
half a granularity step (``x.xx5`` for money), so no threshold can sit
within rounding of a value that a sum of prices produces.

Predicates are tuples:
``("true",)``, ``("false",)``, ``("and", (p, ...))``, ``("or", (p, ...))``,
``("not", p)`` and the linear atom ``("lin", coeffs, const, op)``, meaning
``sum(coef * col) + const (op) 0`` with ``op`` in ``<=``, ``<``, ``==``,
``!=`` and ``coeffs`` a sorted tuple of ``(column, Fraction)``.
"""

from __future__ import annotations

import random
from dataclasses import dataclass
from fractions import Fraction
from typing import Dict, List, Optional, Sequence, Tuple

TRUE = ("true",)
FALSE = ("false",)

# families whose pairs are equivalent by construction: a NEQ verdict on one
# of them is a wrong answer
EQ_FAMILIES = frozenset({"equivalent", "boundary", "rename_storm", "churn_revert"})
FAMILIES = ("equivalent", "semantic", "boundary", "rename_storm",
            "churn_revert", "predicate")
_ONE_INPUT = frozenset({"filter", "project", "aggregate", "sort", "replicate",
                        "sink"})


def frac(x) -> Fraction:
    """A number as the program reads a predicate constant: floats through
    ``limit_denominator(10**9)``."""
    if isinstance(x, Fraction):
        return x
    if isinstance(x, float):
        return Fraction(x).limit_denominator(10**9)
    return Fraction(x)


# -- predicates ----------------------------------------------------------------
def lin(coeffs: Dict[str, Fraction], const, op: str) -> tuple:
    items = tuple(sorted((c, frac(v)) for c, v in coeffs.items() if frac(v) != 0))
    return ("lin", items, frac(const), op)


def cmp(col: str, op: str, value) -> tuple:
    """``col (op) value`` normalized to ``expr (op') 0``."""
    v = frac(value)
    if op in ("<=", "<", "==", "!="):
        return lin({col: 1}, -v, op)
    if op == ">=":
        return lin({col: -1}, v, "<=")
    if op == ">":
        return lin({col: -1}, v, "<")
    raise ValueError(f"bad comparison {op!r}")


def and_(*ps) -> tuple:
    flat: List[tuple] = []
    for p in ps:
        if p[0] == "true":
            continue
        if p[0] == "false":
            return FALSE
        flat.extend(p[1] if p[0] == "and" else (p,))
    if not flat:
        return TRUE
    return flat[0] if len(flat) == 1 else ("and", tuple(flat))


def or_(*ps) -> tuple:
    flat: List[tuple] = []
    for p in ps:
        if p[0] == "false":
            continue
        if p[0] == "true":
            return TRUE
        flat.extend(p[1] if p[0] == "or" else (p,))
    if not flat:
        return FALSE
    return flat[0] if len(flat) == 1 else ("or", tuple(flat))


def pred_from_json(j) -> tuple:
    """``true``, ``[col, op, value]``, ``{"and": [...]}``, ``{"or": [...]}``,
    ``{"not": p}``."""
    if j is True:
        return TRUE
    if isinstance(j, list):
        return cmp(*j)
    (kind, body), = j.items()
    if kind == "and":
        return and_(*map(pred_from_json, body))
    if kind == "or":
        return or_(*map(pred_from_json, body))
    if kind == "not":
        return ("not", pred_from_json(body))
    raise ValueError(f"bad predicate {j!r}")


def pred_columns(p) -> frozenset:
    if p[0] == "lin":
        return frozenset(c for c, _ in p[1])
    if p[0] in ("and", "or"):
        return frozenset().union(*map(pred_columns, p[1]))
    if p[0] == "not":
        return pred_columns(p[1])
    return frozenset()


# -- flows ---------------------------------------------------------------------
Link = Tuple[str, str, int]  # (src, dst, dst_port)


@dataclass(frozen=True)
class Flow:
    """Operators by id (insertion order kept) and links ``(src, dst, port)``.

    An operator is a dict with ``id``, ``op`` and its properties; flows are
    never mutated, every edit returns a new one.
    """

    ops: Dict[str, dict]
    links: Tuple[Link, ...]

    @staticmethod
    def from_json(items: Sequence[dict]) -> "Flow":
        ops: Dict[str, dict] = {}
        links: List[Link] = []
        for it in items:
            op = {"id": it["id"], "op": it["op"]}
            kind = it["op"]
            if kind == "source":
                op["schema"] = tuple(it["schema"])
            elif kind == "filter":
                op["pred"] = pred_from_json(it["pred"])
            elif kind == "join":
                op["on"] = tuple(tuple(p) for p in it["on"])
                op["how"] = it.get("how", "inner")
            elif kind == "project":
                op["cols"] = tuple(
                    (c[0], c[1]) if isinstance(c[1], str)
                    else (c[0], ("expr",) + lin(c[1], c[2], "==")[1:3])
                    for c in it["cols"]
                )
            elif kind == "aggregate":
                op["group_by"] = tuple(it["group_by"])
                op["aggs"] = tuple(tuple(a) for a in it["aggs"])
            elif kind == "sort":
                op["keys"] = tuple((k, bool(a)) for k, a in it["keys"])
            elif kind == "sink":
                op["semantics"] = it.get("semantics", "bag")
            elif kind not in ("replicate", "union"):
                raise ValueError(f"unknown operator {kind!r}")
            ops[op["id"]] = op
            links.extend((src, op["id"], port) for port, src in enumerate(it.get("in", ())))
        return Flow(ops, tuple(links))

    # -- structure -----------------------------------------------------------
    def in_links(self, op_id: str) -> List[Link]:
        return sorted((l for l in self.links if l[1] == op_id), key=lambda l: l[2])

    def out_links(self, op_id: str) -> List[Link]:
        return [l for l in self.links if l[0] == op_id]

    def sinks(self) -> List[str]:
        return [i for i, o in self.ops.items() if o["op"] == "sink"]

    def topo_order(self) -> List[str]:
        indeg = {i: 0 for i in self.ops}
        for _, dst, _ in self.links:
            indeg[dst] += 1
        ready = [i for i in self.ops if indeg[i] == 0]
        out: List[str] = []
        while ready:
            cur = ready.pop(0)
            out.append(cur)
            for _, dst, _ in self.out_links(cur):
                indeg[dst] -= 1
                if indeg[dst] == 0:
                    ready.append(dst)
        if len(out) != len(self.ops):
            raise ValueError("flow has a cycle")
        return out

    def schemas(self) -> Dict[str, List[str]]:
        out: Dict[str, List[str]] = {}
        for op_id in self.topo_order():
            op = self.ops[op_id]
            ins = [out[l[0]] for l in self.in_links(op_id)]
            kind = op["op"]
            if kind == "source":
                out[op_id] = list(op["schema"])
            elif kind == "project":
                out[op_id] = [name for name, _ in op["cols"]]
            elif kind == "join":
                merged = list(ins[0])
                for c in ins[1]:
                    merged.append(c if c not in merged else f"r_{c}")
                out[op_id] = merged
            elif kind == "aggregate":
                out[op_id] = list(op["group_by"]) + [a[2] for a in op["aggs"]]
            else:
                out[op_id] = list(ins[0])
        return out

    # -- rewiring --------------------------------------------------------------
    def _with(self, ops=None, links=None) -> "Flow":
        return Flow(self.ops if ops is None else ops,
                    self.links if links is None else tuple(links))

    def replace(self, op_id: str, **props) -> "Flow":
        ops = dict(self.ops)
        ops[op_id] = {**ops[op_id], **props}
        return self._with(ops=ops)

    def splice(self, link: Link, new_op: dict) -> "Flow":
        """Insert ``new_op`` (one input) on ``link``."""
        src, dst, port = link
        ops = dict(self.ops)
        ops[new_op["id"]] = new_op
        links = [l for l in self.links if l != link]
        links += [(src, new_op["id"], 0), (new_op["id"], dst, port)]
        return self._with(ops=ops, links=links)

    def port0_links(self) -> List[Link]:
        return [l for l in self.links if l[2] == 0]


# -- edit families -------------------------------------------------------------
def draw_const(rng: random.Random, domain) -> Fraction:
    """A constant inside ``domain = [lo, hi, granularity]``, half a
    granularity step off the grid the column's values live on."""
    lo, hi, gran = (frac(x) for x in domain)
    steps = max(1, int((hi - lo) / gran))
    return lo + (rng.randrange(steps) + Fraction(1, 2)) * gran


def _single_atom_filters(flow: Flow) -> List[dict]:
    return [o for o in flow.ops.values()
            if o["op"] == "filter" and o["pred"][0] == "lin" and o["pred"][1]]


def equivalent_edits(flow: Flow, n: int, rng: random.Random, prefix: str,
                     kinds: Optional[List[str]] = None) -> Flow:
    """Calcite-style rewrites: empty project, empty filter, filter swap,
    filter split and a scaled predicate."""
    kinds = kinds or ["empty_project", "empty_filter", "swap_filters",
                      "split_filter", "scale_pred"]
    applied = guard = 0
    while applied < n and guard < 200:
        guard += 1
        kind = rng.choice(kinds)
        if kind in ("empty_project", "empty_filter"):
            l = rng.choice(flow.port0_links())
            new_id = f"{prefix}{'ep' if kind == 'empty_project' else 'ef'}{applied}_{guard}"
            if kind == "empty_project":
                sch = flow.schemas()[l[0]]
                new = {"id": new_id, "op": "project", "cols": tuple((c, c) for c in sch)}
            else:
                new = {"id": new_id, "op": "filter", "pred": TRUE}
            flow = flow.splice(l, new)
            applied += 1
        elif kind == "swap_filters":
            fs = [o for o in flow.ops.values() if o["op"] == "filter"]
            rng.shuffle(fs)
            for f in fs:
                hi_id = f["id"]
                ups = flow.in_links(hi_id)
                lo_id = ups[0][0]
                if flow.ops[lo_id]["op"] != "filter" or len(flow.out_links(lo_id)) != 1:
                    continue
                if len(flow.out_links(hi_id)) != 1:
                    continue
                below = flow.in_links(lo_id)[0]
                above = flow.out_links(hi_id)[0]
                if not pred_columns(f["pred"]) <= set(flow.schemas()[below[0]]):
                    continue
                drop = {below, (lo_id, hi_id, 0), above}
                links = [l for l in flow.links if l not in drop]
                links += [(below[0], hi_id, below[2]), (hi_id, lo_id, 0),
                          (lo_id, above[1], above[2])]
                flow = flow._with(links=links)
                applied += 1
                break
        elif kind == "split_filter":
            fs = [o for o in flow.ops.values()
                  if o["op"] == "filter" and o["pred"][0] == "and"]
            if not fs:
                continue
            f = rng.choice(fs)
            children = f["pred"][1]
            below = flow.in_links(f["id"])[0]
            flow = flow.replace(f["id"], pred=and_(*children[1:]))
            new = {"id": f"{prefix}fs{applied}_{guard}", "op": "filter",
                   "pred": children[0]}
            flow = flow.splice(below, new)
            applied += 1
        elif kind == "scale_pred":
            fs = _single_atom_filters(flow)
            if not fs:
                continue
            f = rng.choice(fs)
            _, coeffs, const, op = f["pred"]
            flow = flow.replace(f["id"], pred=(
                "lin", tuple((c, v * 3) for c, v in coeffs), const * 3, op))
            applied += 1
    return flow


def semantic_edits(flow: Flow, n: int, rng: random.Random, prefix: str,
                   domains: Dict[str, list]) -> Flow:
    """TPC-DS-iterative edits: a filter constant bumped by one, or a new
    ``col < c`` filter on a column with a stated domain."""
    applied = guard = 0
    while applied < n and guard < 100:
        guard += 1
        if rng.choice(["bump_const", "new_filter"]) == "bump_const":
            fs = _single_atom_filters(flow)
            if not fs:
                continue
            f = rng.choice(fs)
            _, coeffs, const, op = f["pred"]
            flow = flow.replace(f["id"], pred=("lin", coeffs, const + 1, op))
            applied += 1
        else:
            l = rng.choice(flow.port0_links())
            cols = [c for c in flow.schemas()[l[0]] if c in domains]
            if not cols:
                continue
            col = rng.choice(cols)
            new = {"id": f"{prefix}nf{applied}_{guard}", "op": "filter",
                   "pred": cmp(col, "<", draw_const(rng, domains[col]))}
            flow = flow.splice(l, new)
            applied += 1
    return flow


def boundary_edit(flow: Flow, hops: int, prefix: str) -> Flow:
    """Two empty filters ``hops`` one-to-one operators apart (paper Fig 26);
    ``ValueError`` when the flow has no chain that long."""
    for first in flow.port0_links():
        path = [first]
        cur = first[1]
        while len(path) <= hops:
            outs = flow.out_links(cur)
            if len(outs) != 1 or flow.ops[cur]["op"] not in _ONE_INPUT:
                break
            path.append(outs[0])
            cur = outs[0][1]
        if len(path) > hops:
            a = {"id": f"{prefix}_a", "op": "filter", "pred": TRUE}
            b = {"id": f"{prefix}_b", "op": "filter", "pred": TRUE}
            q = flow.splice(path[0], a)
            tail = (a["id"], path[0][1], path[0][2]) if hops == 0 else path[hops]
            return q.splice(tail, b)
    raise ValueError(f"no chain with {hops} hops")


def rename_storm(flow: Flow, rng: random.Random, prefix: str):
    """Every interior operator renamed; sources and sinks keep their ids.
    Returns the flow and the full old -> new mapping."""
    interior = sorted(i for i, o in flow.ops.items()
                      if o["op"] not in ("source", "sink"))
    ren = {i: f"{prefix}r{j}_{rng.randrange(16 ** 6):06x}"
           for j, i in enumerate(interior)}
    ops = {ren.get(i, i): {**o, "id": ren.get(i, i)} for i, o in flow.ops.items()}
    links = [(ren.get(s, s), ren.get(d, d), p) for s, d, p in flow.links]
    return Flow(ops, tuple(links)), {i: ren.get(i, i) for i in flow.ops}


def predicate_edit(flow: Flow, rng: random.Random,
                   domains: Dict[str, list]) -> Optional[Flow]:
    """Narrow (``p and x``) or widen (``p or x``) one filter in place, with
    ``x`` a bound on one of its own columns; None when no filter has a
    column with a stated domain."""
    cands = [o for _, o in sorted(flow.ops.items())
             if o["op"] == "filter" and pred_columns(o["pred"]) & set(domains)]
    if not cands:
        return None
    f = rng.choice(cands)
    col = rng.choice(sorted(pred_columns(f["pred"]) & set(domains)))
    atom = cmp(col, rng.choice(["<=", "<", ">=", ">"]),
               draw_const(rng, domains[col]))
    if rng.random() < 0.5:
        pred = and_(f["pred"], atom)
    else:
        pred = or_(f["pred"], atom)
    return flow.replace(f["id"], pred=pred)


@dataclass(frozen=True)
class Version:
    """One version of a session: its flow, the family that made it, and
    the explicit id mapping from the previous version (None = id-stable)."""

    flow: Flow
    kind: str
    mapping: Optional[Dict[str, str]] = None

    @property
    def expected_eq(self) -> bool:
        return self.kind in EQ_FAMILIES


def make_session(base: Flow, mix: Dict[str, float], n_versions: int,
                 max_edits: int, domains: Dict[str, list],
                 rng: random.Random, tag: str) -> List[Version]:
    """``n_versions`` versions grown from ``base``; version 0 is the base."""
    families = [f for f in FAMILIES if mix.get(f, 0) > 0]
    weights = [mix[f] for f in families]
    out = [Version(base, "open")]
    while len(out) < n_versions:
        cur = out[-1].flow
        prefix = f"{tag}v{len(out)}_"
        fam = rng.choices(families, weights=weights)[0]
        if fam == "equivalent":
            n = rng.randint(1, max_edits)
            out.append(Version(equivalent_edits(cur, n, rng, prefix), fam))
        elif fam == "semantic":
            n = rng.randint(1, max_edits)
            out.append(Version(semantic_edits(cur, n, rng, prefix, domains), fam))
        elif fam == "boundary":
            hops = rng.choice([0, 1, 2])
            try:
                q = boundary_edit(cur, hops, f"{prefix}fe")
            except ValueError:
                q = equivalent_edits(cur, 1, rng, prefix, kinds=["empty_filter"])
            out.append(Version(q, fam))
        elif fam == "rename_storm":
            q, mapping = rename_storm(cur, rng, prefix)
            out.append(Version(q, fam, mapping))
        elif fam == "predicate":
            q = predicate_edit(cur, rng, domains)
            if q is None:
                q = semantic_edits(cur, 1, rng, prefix, domains)
            out.append(Version(q, fam))
        else:  # churn_revert: A -> B -> A -> B, both B built from one frozen seed
            churn_seed = rng.randrange(2**31)
            b = equivalent_edits(cur, 1, random.Random(churn_seed), prefix)
            out.append(Version(b, fam))
            out.append(Version(cur, fam))
            out.append(Version(
                equivalent_edits(cur, 1, random.Random(churn_seed), prefix), fam))
    return out[:n_versions]


# -- the bridge to the program ---------------------------------------------------
def to_dag(flow: Flow):
    """The flow as the program's ``DataflowDAG``."""
    from repro.core import dag as D
    from repro.core.predicates import LinCmp, LinExpr, Pred

    def pred(p):
        kind = p[0]
        if kind == "true":
            return Pred.true()
        if kind == "false":
            return Pred.false()
        if kind == "and":
            return Pred.and_(*map(pred, p[1]))
        if kind == "or":
            return Pred.or_(*map(pred, p[1]))
        if kind == "not":
            return Pred.not_(pred(p[1]))
        return Pred.of(LinCmp(LinExpr.make(dict(p[1]), p[2]), p[3]))

    types = {"source": D.SOURCE, "filter": D.FILTER, "join": D.JOIN,
             "project": D.PROJECT, "aggregate": D.AGGREGATE, "sort": D.SORT,
             "sink": D.SINK, "replicate": D.REPLICATE, "union": D.UNION}
    ops = []
    for o in flow.ops.values():
        props = {k: v for k, v in o.items() if k not in ("id", "op")}
        if "pred" in props:
            props["pred"] = pred(props["pred"])
        if "cols" in props:
            props["cols"] = tuple(
                (n, e if isinstance(e, str) else LinExpr.make(dict(e[1]), e[2]))
                for n, e in props["cols"])
        ops.append(D.Operator.make(o["id"], types[o["op"]], **props))
    dag = D.DataflowDAG(ops, [D.Link(s, d, p) for s, d, p in flow.links])
    dag.validate()
    return dag


def to_mapping(mapping: Optional[Dict[str, str]]):
    if mapping is None:
        return None
    from repro.core.edits import EditMapping

    return EditMapping.make(mapping)
