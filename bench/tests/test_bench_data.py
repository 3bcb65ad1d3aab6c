"""The data generator: TPC-DS row counts and key domains, one seed one
byte-identical set of tables."""

import hashlib
import json

import numpy as np

from bench import datagen
from bench.tests.tiny import ROOT, tiny_config

SF1 = {"catalog_sales_rows": 1441548, "catalog_returns_rows": 144067,
       "warehouse_rows": 5, "item_rows": 18000, "date_dim_rows": 73049}


def _digest(tables):
    h = hashlib.sha256()
    for t in sorted(tables):
        for c in sorted(tables[t]):
            h.update(f"{t}.{c}".encode())
            h.update(tables[t][c].tobytes())
    return h.hexdigest()


def test_configuration_states_sf1_row_counts():
    cfg = json.loads((ROOT / "bench/configs/tpcds_q40_sf1.json").read_text())
    for key, n in SF1.items():
        assert cfg[key] == n or key in cfg["reduced"]


def test_row_counts_and_key_domains_at_tiny_scale():
    cfg = tiny_config(rows=3000)
    t = datagen.generate(cfg, 2**33 + 1)
    for name, spec in cfg["tables"].items():
        for col in t[name].values():
            assert len(col) == cfg[spec["rows"]] and col.dtype == np.float64
    cs, cr = t["catalog_sales"], t["catalog_returns"]
    assert np.array_equal(t["item"]["i_item_sk"], np.arange(1, 18001))
    assert np.array_equal(t["date_dim"]["d_date_sk"], np.arange(2415022, 2415022 + 73049))
    assert set(np.unique(cs["cs_warehouse_sk"])) == {1, 2, 3, 4, 5}
    assert cs["cs_item_sk"].min() >= 1 and cs["cs_item_sk"].max() <= 18000
    assert cs["cs_sold_date_sk"].min() >= 2450815 and cs["cs_sold_date_sk"].max() <= 2452640
    orders = cs["cs_order_number"]
    assert orders[0] == 1 and np.all(np.diff(orders) >= 0)
    lines = np.bincount(orders.astype(int))[1:-1]  # the last order may be cut
    assert lines.min() >= 4 and lines.max() <= 14
    sold = set(zip(orders, cs["cs_item_sk"]))
    assert len(sold) == len(orders)  # items of one order are distinct
    returned = list(zip(cr["cr_order_number"], cr["cr_item_sk"]))
    assert len(set(returned)) == len(returned) and set(returned) <= sold
    assert np.all(cr["cr_refunded_cash"] >= 0)
    price = cs["cs_sales_price"]
    assert np.array_equal(np.round(price * 100) / 100, price)  # whole cents


def test_one_seed_gives_byte_identical_tables():
    cfg = tiny_config(rows=2000)
    a = datagen.generate(cfg, 2**33 + 9)
    b = datagen.generate(cfg, 2**33 + 9)
    c = datagen.generate(cfg, 2**33 + 10)
    assert _digest(a) == _digest(b)
    assert _digest(a) != _digest(c)


# columns of each table in the TPC-DS specification; date_dim adds d_after
WIDTHS = {"catalog_sales": 34, "catalog_returns": 27, "warehouse": 14, "item": 22,
          "date_dim": 28}


def test_tables_hold_every_column_of_the_specification():
    cfg = tiny_config(rows=2000)
    t = datagen.generate(cfg, 2**33 + 4)
    base = {op["id"]: op for op in cfg["dataflow"]}
    for name, width in WIDTHS.items():
        schema = base[name]["schema"]
        extra = {"d_after"} if name == "date_dim" else set()
        assert len(t[name]) == width + len(extra)
        assert set(t[name]) == set(cfg["tables"][name]["columns"])
        # each source of the flow scans the columns Q40 reads
        assert set(schema) < set(t[name])
    cs, cr = t["catalog_sales"], t["catalog_returns"]
    sale = {(o, i): k for k, (o, i) in enumerate(zip(cs["cs_order_number"], cs["cs_item_sk"]))}
    rows = [sale[(o, i)] for o, i in zip(cr["cr_order_number"], cr["cr_item_sk"])]
    assert np.array_equal(cr["cr_warehouse_sk"], cs["cs_warehouse_sk"][rows])
    assert np.all(cr["cr_returned_date_sk"] > cs["cs_ship_date_sk"][rows])
