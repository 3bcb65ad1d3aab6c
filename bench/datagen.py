"""Source tables of a configuration, made from a seed with numpy.

Each table in a configuration's ``tables`` names its row count (a
top-level key of the configuration) and one generator per column:

``["seq", start]``                       surrogate keys start, start+1, ...
``["code", k]``                          a dictionary-coded attribute, uniform in 0..k-1
``["cents", lo, hi]``                    uniform whole cents in [lo, hi]
``["uniform_int", lo, hi]``              uniform integers in [lo, hi]
``["fk", table, col]``                   uniform over ``table.col``'s key range
``["copy", col]``                        the same values as ``col``
``["pair_code", col]``                   ``ceil(col / 2)``: two keys share a code
``["flag_ge", col, v]``                  1.0 where ``col >= v``, else 0.0
``["order_number", lo, hi]``             order numbers 1, 2, ... with lo..hi lines each
``["order_distinct_fk", order_col, table, col]``
                                         keys of ``table.col``, distinct within an order
``["per_order", order_col, generator]``  one draw per order, shared by its lines
``["parent", col]``                      (``sample_of`` tables) the sampled row's ``col``
``["cents_upto", col]``                  (``sample_of`` tables) cents in [0, parent ``col``]
``["plus_int", col, lo, hi]``            ``col`` (or the parent's ``col``) plus a uniform integer in [lo, hi]
``["floor_div", col, offset, d, scale, base]``
                                         ``base + scale * floor((col - offset) / d)``
``["mod", col, offset, m, base]``        ``base + (col - offset) mod m``

A ``sample_of`` table draws distinct rows of its parent.  Every column is
float64.  Each table has its own random stream, seeded by ``(seed, table
name)``, so one seed gives byte-identical tables.
"""

from __future__ import annotations

import zlib
from typing import Dict

import numpy as np

Columns = Dict[str, np.ndarray]


def _rng(seed: int, name: str) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), zlib.crc32(name.encode())])


def _cents(rng, lo, hi, n) -> np.ndarray:
    return rng.integers(round(lo * 100), round(hi * 100) + 1, n) / 100.0


def _key_range(tables: Dict[str, Columns], table: str, col: str):
    vals = tables[table][col]
    return int(vals.min()), int(vals.max())


def _orders(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """Order number per line: 1, 1, 1, 1, 2, 2, ... with lo..hi lines each,
    the last order cut at ``n`` lines."""
    lines = rng.integers(lo, hi + 1, n // lo + 1)
    return np.repeat(np.arange(1, len(lines) + 1, dtype=np.float64), lines)[:n]


def _column(spec, rng, n: int, cols: Columns, tables: Dict[str, Columns],
            parent_rows) -> np.ndarray:
    kind = spec[0]
    if kind == "seq":
        return np.arange(spec[1], spec[1] + n, dtype=np.float64)
    if kind == "code":
        return rng.integers(0, spec[1], n).astype(np.float64)
    if kind == "cents":
        return _cents(rng, spec[1], spec[2], n)
    if kind == "uniform_int":
        return rng.integers(spec[1], spec[2] + 1, n).astype(np.float64)
    if kind == "fk":
        lo, hi = _key_range(tables, spec[1], spec[2])
        return rng.integers(lo, hi + 1, n).astype(np.float64)
    if kind == "copy":
        return cols[spec[1]].copy()
    if kind == "pair_code":
        return np.ceil(cols[spec[1]] / 2.0)
    if kind == "flag_ge":
        return (cols[spec[1]] >= spec[2]).astype(np.float64)
    if kind == "order_number":
        return _orders(rng, n, spec[1], spec[2])
    if kind == "order_distinct_fk":
        lo, hi = _key_range(tables, spec[2], spec[3])
        width = hi - lo + 1
        ids = cols[spec[1]].astype(np.int64)
        starts = np.flatnonzero(np.diff(ids, prepend=-1))
        line = np.arange(n) - np.repeat(starts, np.diff(np.append(starts, n)))
        n_orders = len(starts)
        base = rng.integers(0, width, n_orders)
        max_lines = int(line.max()) + 1 if n else 1
        stride = rng.integers(1, max(2, width // max_lines), n_orders)
        k = np.repeat(np.arange(n_orders), np.diff(np.append(starts, n)))
        return ((base[k] + line * stride[k]) % width + lo).astype(np.float64)
    if kind == "per_order":
        order = cols[spec[1]].astype(np.int64)
        per = _column(spec[2], rng, int(order.max()) + 1 if n else 0, cols, tables, None)
        return per[order]
    if kind == "parent":
        return parent_rows[spec[1]]
    if kind == "plus_int":
        src = cols[spec[1]] if spec[1] in cols else parent_rows[spec[1]]
        return src + rng.integers(spec[2], spec[3] + 1, n)
    if kind == "floor_div":
        _, col, offset, d, scale, base = spec
        return base + scale * np.floor((cols[col] - offset) / d)
    if kind == "mod":
        _, col, offset, m, base = spec
        return base + np.mod(cols[col] - offset, m)
    if kind == "cents_upto":
        top = np.round(parent_rows[spec[1]] * 100).astype(np.int64)
        return np.floor(rng.random(n) * (top + 1)) / 100.0
    raise ValueError(f"unknown column generator {kind!r}")


def generate(config: dict, seed: int) -> Dict[str, Columns]:
    """Every source table of ``config``, from ``seed``."""
    tables: Dict[str, Columns] = {}
    # dimensions first: fact columns draw keys from their key ranges
    specs = config["tables"]
    ordered = sorted(specs, key=lambda t: ("sample_of" in specs[t],
                                           any(s[0] in ("fk", "order_distinct_fk")
                                               for s in specs[t]["columns"].values())))
    for name in ordered:
        spec = specs[name]
        n = int(config[spec["rows"]])
        rng = _rng(seed, name)
        parent_rows = None
        if "sample_of" in spec:
            parent = tables[spec["sample_of"]]
            n_parent = len(next(iter(parent.values())))
            idx = rng.choice(n_parent, size=n, replace=False)
            parent_rows = {c: v[idx] for c, v in parent.items()}
        cols: Columns = {}
        for col, cspec in spec["columns"].items():
            cols[col] = _column(cspec, rng, n, cols, tables, parent_rows)
        tables[name] = cols
    return tables
