"""Sessions are fixed by (session seed, analyst, session), whatever the
run's seed.  The equivalence-preserving families keep the reference's
answer."""

import json
import random

import numpy as np
import pytest

from bench import check, dataflow, datagen, harness, reference
from bench.tests.tiny import ROOT, tiny_config


def _traffic(name):
    return json.loads((ROOT / f"bench/traffic/{name}.json").read_text())


def _digests(versions):
    return [v[1].content_digest() for v in versions]


@pytest.mark.parametrize("mix", ["reexec", "rewrite"])
def test_sessions_are_deterministic(mix):
    cfg, traffic = tiny_config(), _traffic(mix)
    a = harness.Sessions(cfg, traffic)
    b = harness.Sessions(cfg, traffic)
    for analyst in range(traffic["analysts"]):
        for number in range(2):
            va, vb = a.session(analyst, number), b.session(analyst, number)
            assert len(va) == traffic["versions_per_session"]
            assert [v[0].kind for v in va] == [v[0].kind for v in vb]
            assert [repr(v[0].flow) for v in va] == [repr(v[0].flow) for v in vb]
    assert a.session(0, 0)[0][0].flow == a.base  # a session opens from the base


def test_a_session_opens_from_the_base_and_grows_by_the_mix():
    cfg, traffic = tiny_config(), _traffic("reexec")
    sessions = harness.Sessions(cfg, traffic)
    for analyst in range(traffic["analysts"]):
        versions = sessions.session(analyst, 0)
        assert versions[0][0].flow == sessions.base and versions[0][0].kind == "open"
        assert {v[0].kind for v in versions[1:]} <= set(traffic["mix"])
    # analysts edit differently
    assert len({repr(_digests(sessions.session(a, 0))) for a in range(traffic["analysts"])}) > 1


@pytest.mark.parametrize("family", sorted(dataflow.EQ_FAMILIES))
def test_equivalent_families_keep_the_reference_answer(family):
    cfg = tiny_config(rows=2000)
    tables = datagen.generate(cfg, 11)
    base = dataflow.Flow.from_json(cfg["dataflow"])
    want = reference.run(base, tables)["sink"]
    rng = random.Random(family)
    versions = dataflow.make_session(base, {family: 1.0}, 8, 2, cfg["domains"], rng, "t")
    for v in versions[1:]:
        got = reference.run(v.flow, tables)["sink"]
        assert check.sink_gap(got, want, "ordered") < 1e-12, v.kind
        dataflow.to_dag(v.flow)  # the program accepts every version


def test_semantic_edits_use_half_step_constants():
    cfg = tiny_config()
    base = dataflow.Flow.from_json(cfg["dataflow"])
    rng = random.Random(3)
    flow = dataflow.semantic_edits(base, 6, rng, "x", cfg["domains"])
    new = [o for i, o in flow.ops.items() if i not in base.ops]
    assert new
    for op in new:
        (col, _), = op["pred"][1]
        lo, _, gran = cfg["domains"][col]
        steps = (-op["pred"][2] - dataflow.frac(lo)) / dataflow.frac(gran)
        assert steps.denominator == 2
    assert np.isfinite(float(new[0]["pred"][2]))
