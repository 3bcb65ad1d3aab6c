"""TPC-DS Q50 on the CPU: the tiny cell's run is correct, the three-key
returns join reaches the device probe and agrees with the plain
reference, the warm-up compiles its probe, and the comparison rejects a
broken answer.  Q50's answers are counts of integer-keyed rows, so the
float32 reference gives the float64 answers exactly: precision cannot be
the control here."""

import numpy as np
import pytest

from bench import check, dataflow, datagen, harness, reference
from bench.tests import tiny_q50


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_q50.tiny_config()
    tables = datagen.generate(cfg, 2**33 + 31)
    return cfg, tables, dataflow.Flow.from_json(cfg["dataflow"])


def _run_program(cfg, tables, **kw):
    from repro.engine.executor import ExecutionPlan

    flow = dataflow.Flow.from_json(cfg["dataflow"])
    return ExecutionPlan(dataflow.to_dag(flow), harness._program_tables(cfg, tables),
                         plane="jax").run(**kw).results


def test_flow_and_tables_have_the_published_shape(setup):
    cfg, tables, base = setup
    widths = {t: len(cols) for t, cols in tables.items()}
    assert widths == {"store_sales": 23, "store_returns": 20, "store": 29, "date_dim": 28}
    assert len(base.ops) == 32 and len(base.sinks()) == 5
    # date_dim feeds two joins; the second one's columns come out as r_
    assert len(base.out_links("date_dim")) == 2
    assert {"r_d_year", "r_d_moy"} <= set(base.schemas()["j_returned"])
    sales = tables["store_sales"]
    per_ticket = {}
    for t, c, s in zip(sales["ss_ticket_number"], sales["ss_customer_sk"], sales["ss_store_sk"]):
        assert per_ticket.setdefault(t, (c, s)) == (c, s)
    lines = np.bincount(sales["ss_ticket_number"].astype(np.int64))[1:]
    assert lines[:-1].min() >= 8 and lines.max() <= 16
    ret = tables["store_returns"]
    lag = ret["sr_returned_date_sk"] - _sold_date_of(tables)
    assert lag.min() >= 1 and lag.max() <= 180


def _sold_date_of(tables):
    sales, ret = tables["store_sales"], tables["store_returns"]
    key = {(t, i, c): d for t, i, c, d in zip(sales["ss_ticket_number"], sales["ss_item_sk"],
                                              sales["ss_customer_sk"], sales["ss_sold_date_sk"])}
    return np.array([key[k] for k in zip(ret["sr_ticket_number"], ret["sr_item_sk"],
                                         ret["sr_customer_sk"])])


def test_every_sink_matches_the_reference_and_float32_changes_nothing(setup):
    cfg, tables, base = setup
    served = _run_program(cfg, tables)
    want = reference.run(base, tables)
    low = reference.run(base, tables, dtype=np.float32)
    limit = cfg["limits"]["sink_gap"]
    for sid in base.sinks():
        t = served[sid]
        assert len(t) > 0, sid
        assert check.sink_gap((list(t.order), dict(t.cols)), want[sid], "ordered") <= limit
        assert check.sink_gap(low[sid], want[sid], "ordered") == 0.0


def test_three_key_join_takes_the_device_probe_and_equals_the_reference(setup):
    from repro.engine.plane import get_plane

    cfg, tables, base = setup
    dag = dataflow.to_dag(base)
    res = _run_program(cfg, tables, keep=["store_sales", "store_returns"])
    plane = get_plane("jax")
    before = plane.device_dispatches()
    out = plane.execute_op(dag.ops["j_returns"], [res["store_sales"], res["store_returns"]])
    assert plane.device_dispatches() > before
    ss = {c: tables["store_sales"][c] for c in base.ops["store_sales"]["schema"]}
    sr = {c: tables["store_returns"][c] for c in base.ops["store_returns"]["schema"]}
    want = reference._join((list(ss), ss), (list(sr), sr), base.ops["j_returns"]["on"], "inner")
    assert len(out) >= len(tables["store_returns"]["sr_ticket_number"])
    assert check.sink_gap((list(out.order), dict(out.cols)), want, "bag") == 0.0


def test_warm_up_compiles_the_three_key_probe(setup):
    from repro.engine.plane import get_plane

    cfg, tables, _ = setup
    plane = get_plane("jax")
    before = plane.device_dispatches()
    assert harness._warm_probe_buckets(cfg, harness._program_tables(cfg, tables)) == 1
    # the full-size call and one per power-of-two bucket of the left input
    n = len(tables["store_sales"]["ss_ticket_number"])
    assert plane.device_dispatches() - before == 1 + (n - 1).bit_length()


def test_tiny_cell_run_is_correct(tmp_path):
    out = tiny_q50.run(tmp_path, control=True)
    line = out.line
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 4 and line["failed"] == 0
    assert line["checks"]["sink_gap"]["value"] == 0.0
    # the float32 control reads what the program reads: every value Q50
    # computes is an integer below 2**24
    assert out.control["readings"]["sink_gap"] == 0.0


def _miscount_each_aggregate(monkeypatch):
    from repro.engine.plane.jax_plane import JaxPlane

    inner = JaxPlane._aggregate

    def miscounted(self, op, inputs):
        out = inner(self, op, inputs)
        last = out.order[-1]
        if len(out):
            out.cols[last] = out.cols[last].copy()
            out.cols[last][0] += 1.0
        return out

    monkeypatch.setattr(JaxPlane, "_aggregate", miscounted)


def test_a_miscounted_bucket_is_not_correct(tmp_path, monkeypatch):
    _miscount_each_aggregate(monkeypatch)
    line = tiny_q50.run(tmp_path).line
    assert line["correct"] is False
    assert line["checks"]["sink_gap"]["value"] > line["checks"]["sink_gap"]["limit"]
