"""The comparison that decides ``correct``.

``sink_gap`` compares one served sink with the reference's: the largest
gap of any value, relative to the largest magnitude in the reference's
column.  Rows are matched in order for an ``ordered`` sink and after a
lexicographic sort of both sides otherwise.  A different column list, row
count or NaN position reads ``STRUCTURE``, far above every limit.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# the reading of a sink whose shape differs from the reference's
STRUCTURE = 1e300


def _rows_sorted(order: List[str], cols: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    if not order:
        return cols
    keys = [np.asarray(cols[c], dtype=np.float64) for c in reversed(order)]
    idx = np.lexsort(keys)
    return {c: np.asarray(cols[c])[idx] for c in order}


def sink_gap(served: Tuple[List[str], Dict[str, np.ndarray]],
             ref: Tuple[List[str], Dict[str, np.ndarray]],
             semantics: str) -> float:
    s_order, s_cols = served
    r_order, r_cols = ref
    if list(s_order) != list(r_order):
        return STRUCTURE
    n_s = len(s_cols[s_order[0]]) if s_order else 0
    n_r = len(r_cols[r_order[0]]) if r_order else 0
    if n_s != n_r:
        return STRUCTURE
    if semantics == "set":
        s_cols = _unique_rows(s_order, s_cols)
        r_cols = _unique_rows(r_order, r_cols)
        if len(s_cols[s_order[0]]) != len(r_cols[r_order[0]]):
            return STRUCTURE
    if semantics != "ordered":
        s_cols = _rows_sorted(s_order, s_cols)
        r_cols = _rows_sorted(r_order, r_cols)
    gap = 0.0
    for c in r_order:
        a = np.asarray(s_cols[c], dtype=np.float64)
        b = np.asarray(r_cols[c], dtype=np.float64)
        if not np.array_equal(np.isnan(a), np.isnan(b)):
            return STRUCTURE
        ok = ~np.isnan(b)
        if not ok.any():
            continue
        scale = float(np.max(np.abs(b[ok])))
        diff = float(np.max(np.abs(a[ok] - b[ok])))
        if diff:
            gap = max(gap, diff / scale if scale else STRUCTURE)
    return gap


def _unique_rows(order, cols):
    if not order:
        return cols
    stacked = np.stack([np.asarray(cols[c], dtype=np.float64) for c in order], axis=1)
    uniq = np.unique(stacked, axis=0)
    return {c: uniq[:, j] for j, c in enumerate(order)}


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    """Each compared number beside its limit; ``ok`` when at or under it."""
    return {name: {"value": readings[name], "limit": limits[name],
                   "ok": readings[name] <= limits[name]}
            for name in limits}
