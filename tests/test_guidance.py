"""Learned search guidance (ISSUE 9): features, models, guided Algorithm 2.

Contract under test (docs/SEARCH_GUIDANCE.md):

  * featurization is deterministic and *parity-locked* — the live search
    path (``features_from_query_pair``) and the harvested-corpus path
    (``features_from_example``) produce the identical vector for the same
    window, so the model sees at inference exactly what it saw in training;
  * training is seeded-deterministic and the JSON artifact round-trips
    bit-for-bit;
  * guidance only schedules work: a guided search agrees with the unguided
    verdict whenever both decide, its certificate replays green, and a
    constant-score guidance degrades byte-identically to the unguided
    search (the tie-break fallback);
  * guided bitmask and reference backends explore identically;
  * the committed pretrained artifact satisfies the feature contract and
    actually steers the W4 acceptance workload to a certificate far inside
    the budget that strands the blind search.
"""

import json

import pytest

from repro.api import default_registry
from repro.api.certificate import Certificate, certificate_from_evidence
from repro.api.config import ConfigError, VeerConfig
from repro.core.verifier import Veer
from repro.learn import (
    FEATURE_NAMES,
    GuidanceModel,
    LogisticModel,
    PRETRAINED_PATH,
    SearchGuidance,
    check_feature_contract,
    features_from_example,
    features_from_query_pair,
    load_guidance,
    train_guidance,
)
from repro.learn.train import _example_from_window, harvest
from repro.workload import WorkloadConfig, dedupe_windows, default_veer_config
from repro.workload.corpus import WindowExample
from repro.workload.flows import apply_equivalent_edits, build_workloads

BUDGET = 3_000


def _pair(n_changes: int, seed: int = 0):
    P = build_workloads()["W4"]
    return P, apply_equivalent_edits(P, n_changes, seed=seed)


def _run(P, Q, *, backend="bitmask", **kw):
    veer = Veer(
        default_registry().build(),
        search_backend=backend,
        max_decompositions=BUDGET,
        **kw,
    )
    v, s, ev = veer.verify_with_evidence(P, Q)
    cert = certificate_from_evidence(ev)
    return v, s, (cert.to_json() if cert is not None else None)


# ---------------------------------------------------------------------------
# features
# ---------------------------------------------------------------------------


def test_feature_vector_matches_declared_names():
    P, Q = _pair(4)
    captured = []

    def observer(ctx, win, out):
        qp = ctx.query_pair(win)
        if qp is not None:
            captured.append(
                features_from_query_pair(
                    qp, len(ctx.units_tuple(win)), ctx.fingerprint(win)
                )
            )

    veer = Veer(
        default_registry().build(),
        ranking=True,
        eager_verify=True,
        window_observer=observer,
    )
    veer.verify(P, Q)
    assert captured, "no windows observed"
    for x in captured:
        assert len(x) == len(FEATURE_NAMES)
        assert all(isinstance(v, float) for v in x)


def test_live_and_corpus_featurization_parity():
    """The vector the search computes for a live window must equal the one
    recomputed from its harvested ``WindowExample`` — train/infer parity."""
    P, Q = _pair(6)
    pairs = []

    def observer(ctx, win, out):
        qp = ctx.query_pair(win)
        ex = _example_from_window(ctx, win, out, meta={})
        live = (
            features_from_query_pair(
                qp, len(ctx.units_tuple(win)), ctx.fingerprint(win)
            )
            if qp is not None
            else None
        )
        pairs.append((live, features_from_example(ex)))

    veer = Veer(
        default_registry().build(),
        ranking=True,
        eager_verify=True,
        window_observer=observer,
    )
    veer.verify(P, Q)
    assert pairs
    for live, harvested in pairs:
        assert live == harvested


def test_featurization_is_deterministic():
    P, Q = _pair(4)
    runs = []
    for _ in range(2):
        vecs = []

        def observer(ctx, win, out, vecs=vecs):
            qp = ctx.query_pair(win)
            if qp is not None:
                vecs.append(
                    features_from_query_pair(
                        qp, len(ctx.units_tuple(win)), ctx.fingerprint(win)
                    )
                )

        Veer(
            default_registry().build(),
            ranking=True,
            eager_verify=True,
            window_observer=observer,
        ).verify(P, Q)
        runs.append(vecs)
    assert runs[0] == runs[1]


# ---------------------------------------------------------------------------
# model: training determinism + artifact round-trip
# ---------------------------------------------------------------------------

_X = [
    [0.0, 1.0, 0.5],
    [1.0, 0.0, 0.2],
    [0.9, 0.1, 0.8],
    [0.1, 0.9, 0.1],
    [0.8, 0.0, 0.9],
    [0.0, 0.8, 0.0],
]
_Y = [0, 1, 1, 0, 1, 0]


def test_logistic_training_is_deterministic():
    a = LogisticModel.train(_X, _Y, seed=7)
    b = LogisticModel.train(_X, _Y, seed=7)
    assert a.weights == b.weights and a.bias == b.bias
    # and it actually separates the toy data
    for x, t in zip(_X, _Y):
        assert (a.predict(x) >= 0.5) == bool(t)


def test_guidance_artifact_roundtrip(tmp_path):
    window = LogisticModel.train(_X, _Y, seed=0)
    model = GuidanceModel(
        feature_names=("f0", "f1", "f2"),
        window=window,
        evs={"udp": LogisticModel.train(_X, _Y, seed=1)},
        meta={"note": "toy"},
    )
    p = tmp_path / "g.json"
    model.save(p)
    loaded = GuidanceModel.load(p)
    assert loaded.feature_names == model.feature_names
    assert loaded.window.weights == model.window.weights
    assert loaded.window.bias == model.window.bias
    assert loaded.evs["udp"].weights == model.evs["udp"].weights
    # bit-for-bit: serialized floats survive the round trip exactly
    assert json.loads(p.read_text()) == json.loads(
        json.dumps(json.loads(p.read_text()))
    )
    for x in _X:
        assert loaded.window.predict(x) == model.window.predict(x)


def test_feature_contract_rejects_mismatched_model():
    model = GuidanceModel(
        feature_names=("not", "the", "contract"),
        window=LogisticModel.constant(3, 0.5),
        evs={},
        meta={},
    )
    with pytest.raises(ValueError):
        check_feature_contract(model)


def test_train_guidance_from_tiny_harvest():
    examples = harvest(seed=3, sessions=1, chain_length=3, max_decompositions=60)
    assert examples
    model, stats = train_guidance(examples, seed=3)
    assert model.feature_names == tuple(FEATURE_NAMES)
    assert stats["trainable"] > 0
    assert set(stats["label_counts"]) <= {"T", "F", "U"}
    x = features_from_example(next(e for e in examples if e.op_hist))
    assert 0.0 <= model.window_score(x) <= 1.0


# ---------------------------------------------------------------------------
# guided search: soundness, identity, fallback
# ---------------------------------------------------------------------------


def test_pretrained_artifact_is_committed_and_contract_clean():
    assert PRETRAINED_PATH.exists(), "pretrained.json must ship with the repo"
    g = load_guidance()
    assert g.model.feature_names == tuple(FEATURE_NAMES)
    assert g.model.meta.get("window", {}).get("n", 0) > 0


def test_guided_backends_explore_identically():
    g = load_guidance()
    P, Q = _pair(8)
    results = {}
    for backend in ("bitmask", "reference"):
        v, s, cert = _run(
            P, Q, backend=backend, ranking=True, eager_verify=True, guidance=g
        )
        results[backend] = (
            v,
            s.decompositions_explored,
            s.decompositions_to_first_certificate,
            dict(s.ev_attempts),
            cert,
        )
    assert results["bitmask"] == results["reference"]


def test_guided_agrees_with_unguided_and_replays():
    g = load_guidance()
    for n in (4, 8):
        P, Q = _pair(n)
        gv, gs, gcert = _run(
            P, Q, ranking=True, eager_verify=True, guidance=g
        )
        uv, us, _ = _run(P, Q, ranking=True)
        if gv is not None and uv is not None:
            assert gv == uv  # scheduling cannot flip a verdict
        assert gv is True and gcert is not None
        report = Certificate.from_json(gcert).replay(P=P, Q=Q)
        assert report.ok, report.summary()


class _NullGuidance:
    """Constant-score guidance: every decomposition ties, every EV order is
    kept — the guided heap must degrade to exactly the unguided search."""

    def decomposition_score(self, ctx, windows):
        return 0.0

    def ev_order(self, ctx, win, valid):
        return valid


def test_constant_guidance_is_byte_identical_to_unguided():
    for n in (4, 8):
        P, Q = _pair(n)
        base_v, base_s, base_cert = _run(P, Q, ranking=True)
        null_v, null_s, null_cert = _run(
            P, Q, ranking=True, guidance=_NullGuidance()
        )
        assert null_v == base_v
        assert null_s.decompositions_explored == base_s.decompositions_explored
        assert (
            null_s.decompositions_to_first_certificate
            == base_s.decompositions_to_first_certificate
        )
        assert null_cert == base_cert


def test_guided_acceptance_on_w4():
    """The ISSUE 9 acceptance shape, in-test: within the budget that strands
    the blind search at UNK, guidance certifies ≥5x inside it and beats the
    unguided ranking outright at the headline size."""
    g = load_guidance()
    P, Q = _pair(12)
    blind_v, blind_s, _ = _run(P, Q)
    assert blind_v is None and blind_s.budget_exhausted
    rank_v, rank_s, _ = _run(P, Q, ranking=True)
    guided_v, guided_s, _ = _run(
        P, Q, ranking=True, eager_verify=True, guidance=g
    )
    assert guided_v is True
    first = guided_s.decompositions_to_first_certificate
    assert first is not None and first * 5 <= BUDGET
    assert rank_v is True
    assert first < rank_s.decompositions_to_first_certificate


# ---------------------------------------------------------------------------
# VeerStats instrumentation
# ---------------------------------------------------------------------------


def test_stats_first_certificate_and_ev_attempts():
    P, Q = _pair(4)
    v, s, _ = _run(P, Q, ranking=True, eager_verify=True)
    assert v is True
    assert s.decompositions_to_first_certificate == s.decompositions_explored
    assert s.ev_attempts and sum(s.ev_attempts.values()) >= s.ev_calls
    # UNK searches leave the marker unset
    uv, us, _ = _run(P, Q)
    assert uv is None and us.decompositions_to_first_certificate is None


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


def test_config_guidance_validation():
    with pytest.raises(ConfigError):
        VeerConfig(guidance="magic").validate()
    with pytest.raises(ConfigError):
        VeerConfig(guidance="none", guidance_path="/x.json").validate()
    VeerConfig(guidance="model").validate()


def test_config_builds_guided_verifier_and_roundtrips():
    cfg = VeerConfig(guidance="model", max_decompositions=BUDGET)
    veer = cfg.build()
    assert isinstance(veer.guidance, SearchGuidance)
    assert VeerConfig.from_json(cfg.to_json()) == cfg
    assert Veer(default_registry().build()).guidance is None
    assert VeerConfig().build().guidance is None


def test_workload_config_threads_guidance():
    wc = WorkloadConfig(guidance="model").validate()
    assert default_veer_config(wc).guidance == "model"
    assert default_veer_config(WorkloadConfig()).guidance == "none"
    with pytest.raises(Exception):
        WorkloadConfig(guidance="zzz").validate()


# ---------------------------------------------------------------------------
# corpus dedupe (satellite 1)
# ---------------------------------------------------------------------------


def _ex(fp, verdict=True):
    return WindowExample(
        workload="W1",
        session_id="s",
        pair_index=0,
        family="equivalent",
        expected="EQ",
        record_kind="search",
        cert_kind="-",
        verdict=verdict,
        ev_name="udp",
        fingerprint=fp,
        units=(0,),
        op_hist={"Filter": 1},
        topology={"n_units": 1, "p_ops": 1, "q_ops": 1, "p_links": 0,
                  "q_links": 0},
    )


def test_dedupe_windows_by_fingerprint():
    examples = [_ex("aa"), _ex("bb"), _ex("aa"), _ex(None), _ex(None)]
    deduped = dedupe_windows(examples)
    # "aa" collapses; the two fingerprint-less examples share a shape key
    assert len(deduped) == 3
    assert deduped[0] is examples[0]  # first occurrence wins
    # distinct shapes without fingerprints survive independently
    other = _ex(None, verdict=False)
    assert len(dedupe_windows([_ex(None), other])) == 2
