"""Plain reference executor for ``bench.dataflow.Flow``.

Straightforward numpy over columns, written from the operator semantics
and sharing no code with the program:

* FILTER keeps the rows where the predicate holds; a linear atom
  ``sum(coef * col) + const (op) 0`` holds within 1e-12, as Veer's
  predicates are defined; any comparison with NaN is false.
* JOIN matches rows whose key tuples are equal; a left-outer join appends
  the unmatched left rows with NaN in every right column; right columns
  whose name the left side already has are renamed ``r_<name>``.
* PROJECT renames columns or evaluates a linear expression.
* AGGREGATE groups by exact key equality (no NaN group keys occur in the
  configurations) and reduces each group; an empty input gives no rows.
* SORT is a stable ascending/descending sort; UNION concatenates;
  REPLICATE and SINK pass their input on.

Row order inside a table is not part of the semantics: sinks are compared
as bags (``bench/check.py``).  ``dtype`` is the precision every value
column is computed in; float32 gives the control that the comparison has
to reject.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.dataflow import Flow

Table = Tuple[List[str], Dict[str, np.ndarray]]  # (column order, columns)


def _pred_mask(p, cols: Dict[str, np.ndarray], n: int, dtype) -> np.ndarray:
    kind = p[0]
    if kind == "true":
        return np.ones(n, dtype=bool)
    if kind == "false":
        return np.zeros(n, dtype=bool)
    if kind == "not":
        return ~_pred_mask(p[1], cols, n, dtype)
    if kind in ("and", "or"):
        masks = [_pred_mask(c, cols, n, dtype) for c in p[1]]
        return np.logical_and.reduce(masks) if kind == "and" else np.logical_or.reduce(masks)
    _, coeffs, const, op = p
    v = _linear(coeffs, const, cols, n, dtype)
    eps = 1e-12
    if op == "<=":
        return v <= eps
    if op == "<":
        return v < -eps
    if op == "==":
        return np.abs(v) <= eps
    return np.abs(v) > eps


def _linear(coeffs, const, cols, n, dtype) -> np.ndarray:
    out = np.full(n, float(const), dtype=dtype)
    for c, v in coeffs:
        out = out + dtype(float(v)) * cols[c].astype(dtype)
    return out


def _key_codes(left: List[np.ndarray], right: List[np.ndarray]):
    """One int64 code per row for the key tuples of both sides."""
    code_l = np.zeros(len(left[0]) if left else 0, dtype=np.int64)
    code_r = np.zeros(len(right[0]) if right else 0, dtype=np.int64)
    for lv, rv in zip(left, right):
        uniq, inv = np.unique(np.concatenate([lv, rv]), return_inverse=True)
        inv = inv.reshape(-1)
        width = np.int64(len(uniq))
        code_l = code_l * width + inv[: len(lv)]
        code_r = code_r * width + inv[len(lv):]
        # keep codes dense so the next key column cannot overflow
        dense, back = np.unique(np.concatenate([code_l, code_r]), return_inverse=True)
        back = back.reshape(-1)
        code_l, code_r = back[: len(lv)], back[len(lv):]
    return code_l, code_r


def _join(lt: Table, rt: Table, on, how: str) -> Table:
    lorder, lcols = lt
    rorder, rcols = rt
    nl = len(lcols[lorder[0]]) if lorder else 0
    code_l, code_r = _key_codes([lcols[a] for a, _ in on], [rcols[b] for _, b in on])
    # NaN keys never match
    for a, b in on:
        code_l = np.where(np.isnan(lcols[a]), -1, code_l)
        code_r = np.where(np.isnan(rcols[b]), -2, code_r)
    r_sorted = np.argsort(code_r, kind="stable")
    keys_sorted = code_r[r_sorted]
    lo = np.searchsorted(keys_sorted, code_l, side="left")
    hi = np.searchsorted(keys_sorted, code_l, side="right")
    counts = hi - lo
    left_idx = np.repeat(np.arange(nl), counts)
    first = np.repeat(lo - np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
    right_idx = r_sorted[first + np.arange(len(left_idx))] if len(left_idx) else np.zeros(0, dtype=np.int64)
    order = list(lorder)
    out: Dict[str, np.ndarray] = {c: lcols[c][left_idx] for c in lorder}
    unmatched = np.flatnonzero(counts == 0) if how == "left_outer" else np.zeros(0, dtype=np.int64)
    for c in lorder:
        out[c] = np.concatenate([out[c], lcols[c][unmatched]])
    for c in rorder:
        name = c if c not in order else f"r_{c}"
        order.append(name)
        vals = rcols[c][right_idx]
        out[name] = np.concatenate([vals, np.full(len(unmatched), np.nan, dtype=vals.dtype)])
    return order, out


def _aggregate(t: Table, group_by, aggs, dtype) -> Table:
    order, cols = t
    n = len(cols[order[0]]) if order else 0
    out_order = list(group_by) + [a[2] for a in aggs]
    if n == 0:
        return out_order, {c: np.zeros(0, dtype=dtype) for c in out_order}
    gid = np.zeros(n, dtype=np.int64)
    for c in group_by:
        _, inv = np.unique(cols[c], return_inverse=True)
        gid = gid * np.int64(n + 1) + inv.reshape(-1)
        _, gid = np.unique(gid, return_inverse=True)
        gid = gid.reshape(-1)
    rows = np.argsort(gid, kind="stable")
    starts = np.flatnonzero(np.diff(gid[rows], prepend=-1))
    counts = np.diff(np.append(starts, n))
    out: Dict[str, np.ndarray] = {c: cols[c][rows[starts]] for c in group_by}
    for fn, c, name in aggs:
        if fn == "count":
            out[name] = counts.astype(dtype)
            continue
        vals = cols[c].astype(dtype)[rows]
        if fn == "sum":
            out[name] = np.add.reduceat(vals, starts)
        elif fn == "avg":
            out[name] = np.add.reduceat(vals, starts) / counts.astype(dtype)
        elif fn == "min":
            out[name] = np.minimum.reduceat(vals, starts)
        elif fn == "max":
            out[name] = np.maximum.reduceat(vals, starts)
        else:
            raise ValueError(f"aggregate {fn!r}")
    return out_order, out


def _sort(t: Table, keys) -> Table:
    order, cols = t
    n = len(cols[order[0]]) if order else 0
    idx = np.arange(n)
    for c, asc in reversed(keys):
        v = cols[c][idx]
        step = np.argsort(v if asc else -v, kind="stable")
        idx = idx[step]
    return order, {c: cols[c][idx] for c in order}


def run(flow: Flow, sources: Dict[str, Dict[str, np.ndarray]],
        dtype=np.float64) -> Dict[str, Table]:
    """Execute ``flow`` on ``sources`` (source id -> columns); returns the
    sink tables by sink id."""
    results: Dict[str, Table] = {}
    remaining = {i: len(flow.out_links(i)) for i in flow.ops}
    sinks: Dict[str, Table] = {}
    for op_id in flow.topo_order():
        op = flow.ops[op_id]
        ins = [results[s] for s, _, _ in flow.in_links(op_id)]
        kind = op["op"]
        if kind == "source":
            cols = sources[op_id]
            t = (list(op["schema"]), {c: cols[c].astype(dtype) for c in op["schema"]})
        elif kind == "filter":
            order, cols = ins[0]
            n = len(cols[order[0]]) if order else 0
            m = _pred_mask(op["pred"], cols, n, dtype)
            t = (order, {c: v[m] for c, v in cols.items()})
        elif kind == "project":
            order, cols = ins[0]
            n = len(cols[order[0]]) if order else 0
            out = {}
            for name, e in op["cols"]:
                out[name] = cols[e] if isinstance(e, str) else _linear(e[1], e[2], cols, n, dtype)
            t = ([name for name, _ in op["cols"]], out)
        elif kind == "join":
            t = _join(ins[0], ins[1], op["on"], op["how"])
        elif kind == "aggregate":
            t = _aggregate(ins[0], op["group_by"], op["aggs"], dtype)
        elif kind == "sort":
            t = _sort(ins[0], op["keys"])
        elif kind == "union":
            (lo, lc), (_, rc) = ins  # by column name, as the left side orders them
            t = (lo, {c: np.concatenate([lc[c], rc[c]]) for c in lo})
        else:  # replicate, sink
            t = ins[0]
        if kind == "sink":
            sinks[op_id] = t
        results[op_id] = t
        for s, _, _ in flow.in_links(op_id):
            remaining[s] -= 1
            if remaining[s] == 0:
                del results[s]
    return sinks
