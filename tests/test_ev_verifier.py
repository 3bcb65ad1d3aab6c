"""EV verdicts + Veer algorithms on paper-style workflow rewrites."""

import numpy as np
import pytest

from helpers import SCHEMA, chain, f, proj_identity, rand_table
from repro.core import dag as D
from repro.core.dag import DataflowDAG, Link, Operator
from repro.core.edits import identity_mapping
from repro.core.ev import EquitasEV, JaxprEV, SpesEV, UDPEV, QueryPair
from repro.core.predicates import LinExpr, Pred
from repro.core.verifier import Veer, make_veer_plus
from repro.core.window import VersionPair
from repro.engine import sink_results_equal


EVS = [EquitasEV(), SpesEV(), UDPEV(), JaxprEV()]


def _check_both(P, Q, expected, veer=None, semantics=D.BAG):
    """Baseline and Veer+ must agree; oracle must not be contradicted."""
    base = veer or Veer([SpesEV(), EquitasEV(), UDPEV()])
    plus = make_veer_plus(base.evs)
    vb, _ = base.verify(P, Q, semantics=semantics)
    vp, _ = plus.verify(P, Q, semantics=semantics)
    assert vb == expected, f"baseline: {vb} != {expected}"
    assert vp == expected, f"veer+: {vp} != {expected}"
    rng = np.random.default_rng(3)
    for _ in range(3):
        t = rand_table(rng)
        equal = sink_results_equal(P, Q, {"src": t} if "src" in P.ops else {})
        if expected is True:
            assert equal
        if expected is False and not equal:
            break


def test_empty_filter_equivalent():
    P = chain(f("f1", "a", ">", 2))
    Q = chain(f("f1", "a", ">", 2), f("fe", "a", "<", 100))
    # fe never filters integers in range but IS semantically restrictive...
    # use a TRUE predicate for a real empty filter
    Q2 = chain(f("f1", "a", ">", 2), Operator.make("fe", D.FILTER, pred=Pred.true()))
    _check_both(P, Q2, True)


def test_filter_reorder_equivalent():
    P = chain(f("f1", "a", ">", 2), f("f2", "b", "<", 5))
    Q = chain(f("f2", "b", "<", 5), f("f1", "a", ">", 2))
    _check_both(P, Q, True)


def test_filter_split_merge():
    P = chain(
        Operator.make(
            "f12", D.FILTER, pred=Pred.and_(Pred.cmp("a", ">", 2), Pred.cmp("b", "<", 5))
        )
    )
    Q = chain(f("f1", "a", ">", 2), f("f2", "b", "<", 5))
    _check_both(P, Q, True)


def test_inequivalent_constant():
    P = chain(f("f1", "a", ">", 2))
    Q = chain(f("f1", "a", ">", 3))
    _check_both(P, Q, False)


def test_filter_past_aggregate():
    P = chain(
        Operator.make("agg", D.AGGREGATE, group_by=("a",), aggs=(("sum", "b", "s"),)),
        f("fg", "a", "<", 4),
    )
    Q = chain(
        f("fg", "a", "<", 4),
        Operator.make("agg", D.AGGREGATE, group_by=("a",), aggs=(("sum", "b", "s"),)),
    )
    _check_both(P, Q, True, veer=Veer([EquitasEV()]))


def test_projection_pushdown():
    P = chain(f("f1", "a", ">", 1), proj_identity("p1"))
    Q = chain(proj_identity("p1"), f("f1", "a", ">", 1))
    _check_both(P, Q, True)


def test_union_requires_udp():
    def mk(swap):
        fa, fb = f("fa", "a", ">", 3), f("fb", "b", "<", 4)
        first, second = (fb, fa) if swap else (fa, fb)
        return DataflowDAG(
            [
                Operator.make("s", D.SOURCE, schema=SCHEMA),
                Operator.make("rep", D.REPLICATE),
                fa, fb,
                Operator.make("u", D.UNION),
                Operator.make("sink", D.SINK, semantics=D.BAG),
            ],
            [
                Link("s", "rep"),
                Link("rep", "fa"),
                Link("rep", "fb"),
                Link(first.id, "u", 0),
                Link(second.id, "u", 1),
                Link("u", "sink"),
            ],
        )

    P, Q = mk(False), mk(True)  # swapped union inputs (bag union commutes)
    v_no_udp, _ = Veer([SpesEV(), EquitasEV()]).verify(P, Q)
    assert v_no_udp is None  # union unsupported → Unknown
    v_udp, _ = Veer([UDPEV()]).verify(P, Q)
    assert v_udp is True


def test_udf_window_jaxpr_ev():
    P = chain(
        Operator.make("u", D.UDF, fn="double_all", out_schema=SCHEMA),
        f("f1", "a", ">", 2),
    )
    # equivalent: filter rewritten to equivalent linear form (2a > 4 ⇔ a > 2)
    Q = chain(
        Operator.make("u", D.UDF, fn="double_all", out_schema=SCHEMA),
        Operator.make(
            "f1", D.FILTER,
            pred=Pred.of(
                __import__("repro.core.predicates", fromlist=["LinCmp"]).LinCmp.make(
                    LinExpr.col("a").scale(2), ">", LinExpr.lit(4)
                )
            ),
        ),
    )
    # relational EVs can't touch the UDF; the window around the filter alone
    # verifies via Spes; the UDF window is identical (CASE1)
    v, _ = Veer([SpesEV()]).verify(P, Q)
    assert v is True


def test_jaxpr_ev_traces_without_touching_a_device():
    """Verifying a window must not initialize a backend: on a machine with
    an accelerator that would take the chip in every verifying process."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        from jax._src import xla_bridge
        from repro.core import dag as D
        from repro.core.ev.jaxpr_ev import _window_jaxpr
        from repro.core.predicates import Pred

        ops = [
            D.Operator.make("s", D.SOURCE, schema=("a", "b")),
            D.Operator.make("f", D.FILTER, pred=Pred.cmp("a", ">", 2)),
            D.Operator.make("u", D.UDF, fn="double_all", out_schema=("a", "b")),
            D.Operator.make("c", D.CLASSIFIER, col="b", out="cls"),
            D.Operator.make("k", D.SINK, semantics=D.BAG),
        ]
        links = [D.Link("s", "f"), D.Link("f", "u"), D.Link("u", "c"),
                 D.Link("c", "k")]
        text = _window_jaxpr(D.DataflowDAG(ops, links), ["k"])
        assert "lambda" in text, text
        assert not xla_bridge._backends, list(xla_bridge._backends)
        print("ok")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p
    )
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_paper_example_mapping_matters():
    """Paper Fig 3: swap of Project and Aggregate under M1 vs M2."""
    P = chain(
        proj_identity("p1"),
        f("fl", "a", ">", 2),
        Operator.make("agg", D.AGGREGATE, group_by=("a",), aggs=(("count", "*", "n"),)),
    )
    Q = chain(
        Operator.make("agg", D.AGGREGATE, group_by=("a",), aggs=(("count", "*", "n"),)),
        f("fl", "a", ">", 2),
        Operator.make("p1", D.PROJECT, cols=(("a", "a"), ("n", "n"))),
    )
    v, _ = Veer([EquitasEV()]).verify(P, Q)
    assert v is True  # push-down canonicalization aligns them


def test_unknown_on_unsupported_change():
    """Paper W8 behavior: edit on a UDF → quick Unknown (no valid window)."""
    P = chain(Operator.make("u", D.UDF, fn="double_all", out_schema=SCHEMA))
    Q = chain(Operator.make("u", D.UDF, fn="add_rowsum", out_schema=SCHEMA))
    v, stats = make_veer_plus([SpesEV(), EquitasEV()]).verify(P, Q)
    assert v is None
    assert stats.decompositions_explored == 0  # segmentation quick-reject


def test_stats_optimizations_reduce_exploration():
    P = chain(f("f1", "a", ">", 1), f("f2", "b", "<", 5), f("f3", "c", ">", 0),
              proj_identity("p1"), f("f4", "a", "<", 6))
    Q = chain(f("f2", "b", "<", 5), f("f1", "a", ">", 1), f("f3", "c", ">", 0),
              proj_identity("p1"), f("f4", "a", "<", 6))
    base = Veer([SpesEV()])
    plus = make_veer_plus([SpesEV()])
    vb, sb = base.verify(P, Q)
    vp, sp = plus.verify(P, Q)
    assert vb is True and vp is True
    assert sp.decompositions_explored <= sb.decompositions_explored


def test_symbolic_fast_inequivalence():
    P = chain(Operator.make("p", D.PROJECT, cols=(("a", "a"), ("b", "b"))))
    Q = chain(Operator.make("p", D.PROJECT, cols=(("a", "a"),)))
    plus = make_veer_plus([SpesEV()])
    v, stats = plus.verify(P, Q)
    assert v is False
    assert stats.fast_inequivalence_hit


def test_algorithm1_single_edit():
    P = chain(f("f1", "a", ">", 2), proj_identity("p1"))
    Q = chain(f("f1", "a", ">", 2), Operator.make("fe", D.FILTER, pred=Pred.true()),
              proj_identity("p1"))
    veer = Veer([SpesEV()])
    v, stats = veer.verify_single_edit(P, Q)
    assert v is True
    mcws = veer.maximal_covering_windows(P, Q)
    assert mcws  # at least one MCW found


def test_ev_restriction_flags():
    assert SpesEV().restriction_monotonic
    assert not EquitasEV().restriction_monotonic
    assert SpesEV().can_prove_inequivalence
    assert not EquitasEV().can_prove_inequivalence
    assert not JaxprEV().can_prove_inequivalence
