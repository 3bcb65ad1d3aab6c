"""The plain reference agrees with the program, its float32 control does
not, and the Q40 returns join is the one that reaches the device probe."""

import numpy as np
import pytest

from bench import check, dataflow, datagen, harness, reference
from bench.tests.tiny import tiny_config


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config(rows=4000)
    tables = datagen.generate(cfg, 2**33 + 21)
    base = dataflow.Flow.from_json(cfg["dataflow"])
    return cfg, tables, base


def _program_sink(cfg, tables, flow):
    from repro.engine.executor import ExecutionPlan

    res = ExecutionPlan(dataflow.to_dag(flow), harness._program_tables(cfg, tables),
                        plane="jax").run()
    t = res.results["sink"]
    return list(t.order), dict(t.cols)


def test_reference_matches_the_program_and_the_control_does_not(setup):
    cfg, tables, base = setup
    served = _program_sink(cfg, tables, base)
    want = reference.run(base, tables)["sink"]
    assert len(want[1]["sales"]) > 50
    limit = cfg["limits"]["sink_gap"]
    assert check.sink_gap(served, want, "ordered") <= limit
    low = reference.run(base, tables, dtype=np.float32)["sink"]
    assert check.sink_gap(low, want, "ordered") > limit


def test_sink_gap_reads_structure_and_values():
    order = ["k", "v"]
    want = (order, {"k": np.array([1.0, 2.0]), "v": np.array([10.0, np.nan])})
    same = (order, {"k": np.array([2.0, 1.0]), "v": np.array([np.nan, 10.0])})
    assert check.sink_gap(same, want, "bag") == 0.0
    assert check.sink_gap(same, want, "ordered") == check.STRUCTURE
    off = (order, {"k": np.array([1.0, 2.0]), "v": np.array([10.5, np.nan])})
    assert check.sink_gap(off, want, "ordered") == pytest.approx(0.05)
    short = (order, {"k": np.array([1.0]), "v": np.array([10.0])})
    assert check.sink_gap(short, want, "bag") == check.STRUCTURE


def test_returns_join_takes_the_device_probe_and_single_key_joins_do_not():
    """At 40,000 sales rows (and above) the (order, item) codes are sparse
    enough for the probe branch; single-key codes are always dense."""
    from repro.engine.executor import ExecutionPlan
    from repro.engine.plane import get_plane

    cfg = tiny_config(rows=40000)
    tables = datagen.generate(cfg, 2**33 + 22)
    base = dataflow.Flow.from_json(cfg["dataflow"])
    dag = dataflow.to_dag(base)
    joins = [i for i in base.topo_order() if base.ops[i]["op"] == "join"]
    inputs = sorted({s for j in joins for s, _, _ in base.in_links(j)})
    res = ExecutionPlan(dag, harness._program_tables(cfg, tables), plane="jax").run(keep=inputs)
    plane = get_plane("jax")
    dispatched = {}
    for j in joins:
        left, right = (res.results[s] for s, _, _ in base.in_links(j))
        before = plane.device_dispatches()
        plane.execute_op(dag.ops[j], [left, right])
        dispatched[j] = plane.device_dispatches() > before
    assert dispatched == {"j_returns": True, "j_warehouse": False, "j_item": False,
                          "j_date": False}


def test_single_key_joins_stay_on_the_host_at_q18_sizes():
    """A Q18-shaped single-key join (sales to customer demographics, whose
    keys cover the dimension) never reaches the device probe."""
    from repro.core import dag as D
    from repro.engine.plane import get_plane
    from repro.engine.table import Table

    rng = np.random.default_rng(5)
    n_cd = 19208
    sales = Table({"cs_bill_cdemo_sk": rng.integers(1, n_cd + 1, 14415).astype(float)})
    cd = Table({"cd_demo_sk": np.arange(1, n_cd + 1, dtype=float),
                "cd_dep_count": rng.integers(0, 7, n_cd).astype(float)})
    op = D.Operator.make("j", D.JOIN, on=(("cs_bill_cdemo_sk", "cd_demo_sk"),), how="inner")
    plane = get_plane("jax")
    before = plane.device_dispatches()
    out = plane.execute_op(op, [sales, cd])
    assert plane.device_dispatches() == before and len(out) == 14415
