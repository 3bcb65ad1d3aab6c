"""Canonical key machinery shared by the engine planes.

The reference engine (``repro.engine.ops_impl``) builds hash-join indexes,
aggregate groups and distinct sets with *Python dict keys*: every row value
passes through ``keyval`` (round floats to 9 digits, unwrap numpy scalars)
and equality is Python ``==`` on the results.  That gives three semantics
the vectorized plane must replicate **exactly**:

  * rounded floats compare by value, so ``-0.0`` and ``0.0`` collapse and
    ``1.0000000001`` joins ``0.9999999999`` onto ``1.0``'s slot whenever
    their 9-digit roundings coincide;
  * each ``NaN`` is its own dict key (``nan != nan`` and the objects are
    distinct), so NaN join keys never match and every NaN row is its own
    aggregate group — while ``repr``-keyed paths (DISTINCT) collapse all
    NaNs to one;
  * Python ``round`` is *not* ``np.round`` (different tie/precision
    behavior on ~4% of uniform floats), so rounding must go through the
    real ``round``.

``column_codes`` squares the circle without per-row Python: factorize the
column with ``np.unique`` (vectorized), then apply ``keyval``-keyed dict
compression only to the **unique** values — O(distinct) Python work, exact
dict-key equality by construction.  ``combine_codes`` folds several code
columns into one row key, re-compressing at each step so values stay far
from int64 overflow.

Most key columns need no sort.  Where a column is bool or integer, or
float with every non-NaN value finite, integral and within ``+-2**53``,
and its values span at most ``4 * len``, ``column_codes`` reads the codes
off a presence bitmap over ``[min, max]`` (over ``[0, max]`` where the
values already lie in ``[0, 4 * len]``): the rank of ``v`` among the
present values, O(n + range).  That is exactly ``np.unique``'s inverse
index: both count the distinct values below ``v``.  The cast to int64 is
exact (integers within ``2**53`` are float64s), and ``-0.0`` casts to 0,
so the zeros share a rank as they share a ``np.unique`` slot.  Distinct
integers lie at least 1 apart, so no two share a 9-digit rounding and the
``keyval`` remap is the identity.  NaNs take the numbers the sort gives
them: after the ``k`` distinct values, ``k`` for every NaN, or under
``nan_distinct`` ``k + 1`` on in row order (``np.unique``'s one NaN slot
is counted first).  Every other column takes the sort; the choice reads
only the data.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

_EXACT = float(2**53)
_INT64_MAX = int(np.iinfo(np.int64).max)


def keyval(v):
    """The reference engine's dict-key canonicalization (one scalar)."""
    if isinstance(v, (np.floating, float)):
        return round(float(v), 9)
    if isinstance(v, np.integer):
        return int(v)
    return v


def column_codes(arr: np.ndarray, *, nan_distinct: bool) -> np.ndarray:
    """Dense int64 codes with ``keyval``-equality semantics, vectorized.

    Two rows get the same code iff their ``keyval`` canonicalizations are
    equal as Python dict keys.  ``nan_distinct=True`` gives every NaN row a
    fresh code (the join/aggregate dict-key behavior: ``nan != nan``);
    ``nan_distinct=False`` collapses all NaNs to one code (the
    ``repr``-keyed DISTINCT behavior, where every NaN prints ``nan``).

    Object-dtype columns are not supported — callers fall back to the
    reference plane for those.
    """
    return factorize(arr, nan_distinct=nan_distinct)[0]


def factorize(arr: np.ndarray, *, nan_distinct: bool) -> Tuple[np.ndarray, bool]:
    """``column_codes`` and whether they took the ``np.unique`` sort
    (``False``: the sort-free rank path, or an empty column)."""
    arr = np.asarray(arr)
    if arr.dtype == object:
        raise TypeError("column_codes does not support object columns")
    if arr.size == 0:
        return np.zeros(0, dtype=np.int64), False
    codes = _rank_codes(arr.reshape(-1), nan_distinct)
    if codes is not None:
        return codes, False
    return _sorted_codes(arr, nan_distinct), True


def _rank_codes(flat: np.ndarray, nan_distinct: bool) -> Optional[np.ndarray]:
    """The sort-free codes of an integral column whose non-NaN values span
    at most ``4 * len(flat)``, or ``None`` where the column is not one.

    The codes are the ranks of the values among the distinct values, read
    off a presence bitmap; NaNs are numbered after them as ``_sorted_codes``
    numbers them.  Each numpy call releases the interpreter lock and waits
    to take it back, which costs more than its pass while other threads
    run Python, so the path makes few calls.
    """
    n = flat.size
    kind = flat.dtype.kind
    nan_mask = None
    if kind == "f" and flat.dtype.itemsize <= 8:
        vals = flat
        lo, hi = float(vals.min()), float(vals.max())
        if lo != lo:  # min and max propagate NaN: mask only where there is one
            nan_mask = np.isnan(flat)
            vals = flat[~nan_mask]
            lo, hi = (float(vals.min()), float(vals.max())) if vals.size else (0.0, 0.0)
        # inf fails the bounds; within +-2**53 every integer is a float64,
        # so the cast below is exact where the value is integral
        if not (-_EXACT <= lo and hi <= _EXACT) or hi - lo > 4 * n:
            return None
        off = vals.astype(np.int64)
        if not (off == vals).all():
            return None
        lo, hi = int(lo), int(hi)
    elif kind in "biu":
        lo, hi = int(flat.min()), int(flat.max())
        if hi - lo > 4 * n or hi > _INT64_MAX:
            return None
        off = flat.astype(np.int64, copy=False)
    else:
        return None
    if lo < 0 or hi > 4 * n:  # values already in [0, 4n] index the bitmap as they are
        off = off - lo
        hi -= lo
    present = np.zeros(hi + 1, dtype=bool)
    present[off] = True
    rank = np.empty(hi + 1, dtype=np.int64)  # distinct values below each offset
    rank[0] = 0
    np.cumsum(present[:-1], out=rank[1:])
    codes = rank[off]
    if nan_mask is None:
        return codes
    # _sorted_codes: np.unique's one NaN slot follows the k non-NaN values;
    # nan_distinct numbers the NaN rows after that slot, in row order
    k = int(rank[-1] + present[-1])  # the distinct values
    out = np.empty(n, dtype=np.int64)
    out[~nan_mask] = codes
    n_nan = n - len(off)
    out[nan_mask] = (k + 1 + np.arange(n_nan, dtype=np.int64)) if nan_distinct else k
    return out


def _sorted_codes(arr: np.ndarray, nan_distinct: bool) -> np.ndarray:
    """``column_codes`` by ``np.unique``: any column."""
    uniq, inv = np.unique(arr, return_inverse=True)
    inv = inv.reshape(-1).astype(np.int64)
    # fast path: the keyval remap can only merge uniques beyond what
    # np.unique already merged (-0.0 with 0.0, equal values) when two
    # uniques share a 9-digit rounding — which forces |a-b| <~ 1.1e-9.
    # Integers/bools can never merge; floats whose adjacent uniques are
    # all farther apart than 1e-8 can never merge either, so the remap is
    # the identity and ``inv`` is already the code column.
    merge_possible = False
    n_slots = len(uniq)
    if arr.dtype.kind == "f":
        fu = uniq[~np.isnan(uniq)] if np.isnan(uniq[-1]) else uniq
        merge_possible = len(fu) > 1 and float(np.min(np.diff(fu))) <= 1e-8
    if not merge_possible:
        codes = inv
    else:
        # dict-compress only the uniques: exact Python round/==/hash
        # semantics at O(distinct) cost
        slots: dict = {}
        remap = np.empty(len(uniq), dtype=np.int64)
        for i, u in enumerate(uniq):
            k = keyval(u)
            remap[i] = slots.setdefault(k, len(slots))
        codes = remap[inv]
        n_slots = len(slots)
    if arr.dtype.kind == "f":
        nan_mask = np.isnan(arr)
        if nan_mask.any() and nan_distinct:
            # np.unique collapsed the NaNs; give each NaN row its own code,
            # numbered in row order so code order tracks insertion order
            base = np.int64(n_slots)
            codes[nan_mask] = base + np.arange(
                int(nan_mask.sum()), dtype=np.int64
            )
    return codes


def combine_codes(code_cols: Sequence[np.ndarray]) -> np.ndarray:
    """Fold per-column codes into one int64 row key (tuple equality).

    Rows are equal under the combined code iff they are equal under every
    input code — the vectorized analogue of keying a dict on the tuple of
    per-column ``keyval`` results.  Output codes are **not** compressed to
    a dense range (callers argsort, run-partition or re-unique them; only
    equality matters); a fold re-compresses through ``np.unique`` only
    when the running value range would otherwise overflow int64.
    """
    cols: List[np.ndarray] = [np.asarray(c, dtype=np.int64) for c in code_cols]
    if not cols:
        raise ValueError("combine_codes needs at least one code column")
    limit = np.iinfo(np.int64).max // 4
    out = cols[0]
    out_max = int(out.max()) if len(out) else 0
    for c in cols[1:]:
        c_max = int(c.max()) if len(c) else 0
        mult = c_max + 1
        if out_max > limit // mult:
            # compress before the fold; compressed codes are < n, and any
            # single column's codes are < 2n, so n*(2n) stays far below
            # int64 for every feasible table
            _, out = np.unique(out, return_inverse=True)
            out = out.reshape(-1).astype(np.int64)
            out_max = int(out.max()) if len(out) else 0
        out = out * np.int64(mult) + c
        out_max = out_max * mult + c_max
    return out


def run_bounds(codes: np.ndarray):
    """Adjacent-run decomposition of ``codes``: ``(run_id, starts, ends)``.

    ``run_id[i]`` is the index of the run row ``i`` belongs to; ``starts``
    and ``ends`` are the inclusive run boundaries.  Used by the vectorized
    descending-sort stability fix and the segment layout of the aggregate
    lowering.
    """
    n = len(codes)
    if n == 0:
        empty = np.zeros(0, dtype=np.int64)
        return empty, empty, empty
    change = np.empty(n, dtype=bool)
    change[0] = True
    np.not_equal(codes[1:], codes[:-1], out=change[1:])
    run_id = np.cumsum(change) - 1
    starts = np.flatnonzero(change)
    ends = np.append(starts[1:], n) - 1
    return run_id, starts, ends
