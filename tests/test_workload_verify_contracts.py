"""Verification contracts of the flows W1-W8 (``repro.workload.flows``).

Per flow: a pair made by equivalent edits is certified True and its
certificate replays green bound to the pair; a pair made by an
inequivalent edit is never certified True.  The flow library also imports
without the repository root on ``sys.path``.
"""

import os
import pathlib
import subprocess
import sys

import pytest

from repro.api import VeerConfig, default_registry
from repro.service import VersionChainSession
from repro.workload.flows import WORKLOADS, apply_equivalent_edits, apply_inequivalent_edits

BUDGET = 2000
SRC = pathlib.Path(__file__).resolve().parent.parent / "src"

EQUIVALENT_EDITS = {
    "empty_project": dict(n=1, seed=3, kinds=["empty_project"]),
    "empty_filter": dict(n=1, seed=3, kinds=["empty_filter"]),
    "mixed3": dict(n=3, seed=5),
}


def _verify(P, Q):
    session = VersionChainSession(config=VeerConfig(max_decompositions=BUDGET))
    session.submit(P)
    return session.submit(Q)


@pytest.mark.parametrize("edit", sorted(EQUIVALENT_EDITS))
@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_equivalent_edit_certified_and_replays(name, edit):
    P = WORKLOADS[name]()
    Q = apply_equivalent_edits(P, **EQUIVALENT_EDITS[edit])
    assert Q.signature() != P.signature()
    report = _verify(P, Q)
    assert report.verdict is True
    replay = report.certificate.replay(default_registry(), P, Q)
    assert replay.ok, replay


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_inequivalent_edit_never_certified_equal(name, seed):
    P = WORKLOADS[name]()
    Q = apply_inequivalent_edits(P, 1, seed=seed)
    assert Q.signature() != P.signature()
    assert _verify(P, Q).verdict is not True


@pytest.mark.parametrize("package", ["repro.workload", "repro.learn"])
def test_library_imports_outside_repo_root(package, tmp_path):
    env = dict(os.environ, PYTHONPATH=str(SRC))
    out = subprocess.run(
        [sys.executable, "-c", f"import {package}"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
