"""Execution contracts of the flows W1-W8 (``repro.workload.flows``).

Per flow, at 30 rows per source:

  * the jax plane's sinks are byte-identical to the numpy reference
    plane's;
  * a ``VersionChainSession`` serving a version after three equivalent
    edits returns, under every ``exec_mode``, exactly the sinks of a full
    ``execute()`` of that version.
"""

import pytest

from repro.api import VeerConfig
from repro.engine import InMemoryMaterializationStore, execute, tables_identical
from repro.service import VersionChainSession
from repro.workload.flows import WORKLOADS, apply_equivalent_edits, random_tables

BUDGET = 2000


def _assert_sinks_identical(got, want):
    assert set(got) == set(want)
    for sink, table in want.items():
        assert tables_identical(got[sink], table), sink


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_planes_agree(name):
    P = WORKLOADS[name]()
    src = random_tables(P, seed=0, n=30)
    _assert_sinks_identical(execute(P, src, plane="jax"), execute(P, src, plane="numpy"))


@pytest.mark.parametrize("mode", ["full", "reuse", "delta"])
@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_session_sinks_match_full_execution(name, mode):
    P = WORKLOADS[name]()
    Q = apply_equivalent_edits(P, 3, seed=5)
    src = random_tables(P, seed=0, n=30)
    session = VersionChainSession(
        config=VeerConfig(exec_mode=mode, max_decompositions=BUDGET),
        materialization_store=InMemoryMaterializationStore(),
    )
    session.submit(P, sources=src)
    report = session.submit(Q, sources=src)
    _assert_sinks_identical(report.results, execute(Q, src))
