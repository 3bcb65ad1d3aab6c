"""VersionChainSession: chain verification with memoized EV verdicts.

Acceptance criteria from the chain-service issue: on a deterministic
10-version chain, second-and-later pairs show cache hits and total EV calls
beat the no-cache baseline.
"""

import pytest

from repro.core import dag as D
from repro.core.dag import DataflowDAG, Link, Operator
from repro.core.edits import apply_transformation, diff, identity_mapping
from repro.core.ev import EquitasEV, SpesEV, UDPEV, VerdictCache
from repro.core.predicates import Pred
from repro.core.verifier import make_veer_plus
from repro.service import VersionChainSession, verify_chain
from repro.service.synthetic import make_chain

op = Operator.make

EVS = lambda: [EquitasEV(), SpesEV(), UDPEV()]


def test_chain_versions_are_valid_and_one_to_two_changes_apart():
    """Each version touches 1-2 rewrite sites (branches) of its predecessor.

    (A single filter swap shows up as several grouped link changes in
    Veer's change model; the iterative-analytics claim is about user-level
    rewrites, i.e. distinct branches touched.)
    """
    chain = make_chain(10)
    assert len(chain) == 10
    for v in chain:
        v.validate()
    for a, b in zip(chain, chain[1:]):
        from repro.core.window import VersionPair

        pair = VersionPair(a, b, identity_mapping(a, b))
        assert pair.changes
        import re

        touched_branches = {
            re.sub(r"\D", "", pair.units[u].p or pair.units[u].q)
            for c in pair.changes
            for u in c.required_units
        }
        assert 1 <= len(touched_branches) <= 2


def test_deterministic_chain_meets_acceptance_criteria():
    chain = make_chain(10)
    report = verify_chain(chain, evs=EVS())

    # every consecutive pair is equivalent by construction
    assert all(v is True for v in report.verdicts)
    # second-and-later pairs hit the verdict cache
    assert all(p.cache_hits > 0 for p in report.pairs[1:])
    # pair k gets cheaper than pair 1
    assert report.pairs[0].ev_calls > 0
    assert min(p.ev_calls for p in report.pairs[1:]) == 0

    # total EV calls measurably below the no-cache baseline
    baseline_calls = 0
    for a, b in zip(chain, chain[1:]):
        verdict, stats = make_veer_plus(EVS()).verify(a, b)
        assert verdict is True
        baseline_calls += stats.ev_calls
    assert report.total_ev_calls < baseline_calls


def test_session_incremental_api():
    session = VersionChainSession(EVS())
    chain = make_chain(4)
    assert session.submit(chain[0]) is None  # nothing to verify yet
    r1 = session.submit(chain[1])
    r2 = session.submit(chain[2])
    assert r1.equivalent and r2.equivalent
    assert r1.index == 1 and r2.index == 2
    assert len(session.report().pairs) == 2
    assert "pairs" in session.report().summary()


def test_session_persists_across_instances(tmp_path):
    path = tmp_path / "verdicts.json"
    chain = make_chain(6)

    with VersionChainSession(EVS(), cache_path=path) as s1:
        for v in chain:
            s1.submit(v)
    assert path.exists()
    cold_calls = s1.report().total_ev_calls
    assert cold_calls > 0

    s2 = VersionChainSession(EVS(), cache_path=path)
    for v in chain:
        s2.submit(v)
    assert all(v is True for v in s2.report().verdicts)
    assert s2.report().total_ev_calls == 0  # fully warm
    assert s2.report().total_cache_hits > 0


def test_session_flags_inequivalent_update():
    """A semantically different version must not be reported equivalent."""
    base = make_chain(2)[0]
    tightened = base.replace_op(
        op("fa0", D.FILTER, pred=Pred.cmp("a", ">", 4))
    )
    session = VersionChainSession(EVS())
    session.submit(base)
    r = session.submit(tightened)
    assert r.verdict is not True  # False or Unknown, never a wrong True


def test_session_arg_validation(tmp_path):
    with pytest.raises(ValueError):
        VersionChainSession(
            cache=VerdictCache(), cache_path=tmp_path / "x.json"
        )
    with pytest.raises(ValueError):
        VersionChainSession(EVS(), veer=make_veer_plus(EVS()))
    with pytest.raises(ValueError):
        verify_chain(make_chain(3), mappings=[None])  # wrong mapping count


def test_chain_bench_smoke():
    import pathlib
    import subprocess
    import sys

    script = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "chain_bench.py"
    out = subprocess.run(
        [sys.executable, str(script), "--smoke", "--versions", "4"],
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
