"""Cross-process differential tests: fleet answers ≡ sequential answers.

The scale-out claim worth the most scrutiny is not that the fleet is
fast — it is that sharding, process boundaries, journal replay, and the
shared cache tier change *nothing observable*.  These tests push seeded
``SessionGenerator`` sessions through every fleet shape (1, 2, and 4
worker processes × local and remote shared tier) and assert the answers
are **byte-identical** to a sequential single-process reference:

  * the verdict of every pair,
  * ``decompositions_explored`` (the search structure itself — verdict
    cache warmth may save EV *calls*, but it must never change what the
    search explored),
  * the certificate's canonical JSON,
  * the canonical byte encoding of every executed sink table.

The reference is one fresh ``VersionChainSession`` per session (own
verdict cache, own pair cache, own store): exactly what a user running
the chain alone on one process would get.  Generated sessions never
collide across clients (each session's operators carry a unique prefix),
so intra-client reuse — e.g. churn/revert pairs re-hitting the pair
cache — is the same on both sides, while cross-client tier warmth can
only avoid EV calls, never alter answers.

A hypothesis property test widens the seed space where hypothesis is
installed; the seeded sweep below always runs.
"""

import shutil
import tempfile

import pytest

from repro.api.config import VeerConfig
from repro.engine.store import InMemoryMaterializationStore
from repro.service import VerificationFleet
from repro.service.chain import VersionChainSession
from repro.service.pair_cache import PairVerdictCache
from repro.workload.config import WorkloadConfig
from repro.workload.generator import SessionGenerator
from repro.workload.replay import REPLAY_EVS, canonical_results_bytes

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAS_HYPOTHESIS = False

CONFIG = VeerConfig(evs=REPLAY_EVS, max_decompositions=60)

FLEET_SHAPES = [
    (1, "local"),
    (2, "local"),
    (4, "local"),
    (1, "remote"),
    (2, "remote"),
    (4, "remote"),
]


def _workload(seed: int, sessions: int = 3, chain_length: int = 5) -> WorkloadConfig:
    return WorkloadConfig(
        seed=seed,
        sessions=sessions,
        clients=sessions,
        chain_length=chain_length,
        max_decompositions=60,
    )


def _generate(wc: WorkloadConfig):
    gen = SessionGenerator(wc)
    return [gen.session(i) for i in range(wc.sessions)]


def _signature(session, reports):
    """The observable answer trace the differential oracle compares:
    everything a user could act on, nothing timing- or warmth-dependent."""
    trace = []
    for k, report in enumerate(reports):
        if report is None:
            trace.append(("none",))
            continue
        dag = session.versions[k]
        sinks = (
            sorted(canonical_results_bytes(dag, report.results).items())
            if report.results is not None
            else None
        )
        if k == 0:
            trace.append(("first", sinks))
            continue
        trace.append(
            (
                report.verdict,
                report.stats.decompositions_explored,
                report.certified,
                report.certificate.to_json()
                if report.certificate is not None
                else None,
                sinks,
            )
        )
    return trace


def _sequential_reference(sessions):
    """One fresh single-process chain session per edit session."""
    out = {}
    for s in sessions:
        chain = VersionChainSession(
            config=CONFIG,
            materialization_store=InMemoryMaterializationStore(),
            pair_cache=PairVerdictCache(),
        )
        reports = []
        for k, version in enumerate(s.versions):
            mapping = s.pairs[k - 1].mapping if k > 0 else None
            reports.append(chain.submit(version, mapping, sources=s.sources))
        out[s.session_id] = _signature(s, reports)
    return out


def _fleet_run(sessions, workers: int, shared_tier: str, tier_dir):
    cfg = CONFIG.replace(
        shared_tier=shared_tier,
        tier_dir=str(tier_dir) if shared_tier == "remote" else None,
    )
    futures = {s.session_id: [] for s in sessions}
    with VerificationFleet(workers, config=cfg) as fleet:
        # round-robin like the replay driver: all clients in flight at once
        for k in range(max(len(s.versions) for s in sessions)):
            for s in sessions:
                if k < len(s.versions):
                    mapping = s.pairs[k - 1].mapping if k > 0 else None
                    futures[s.session_id].append(
                        fleet.submit(
                            s.session_id, s.versions[k], mapping,
                            sources=s.sources,
                        )
                    )
        report = fleet.drain()
    assert not report.errors, report.errors
    return {
        s.session_id: _signature(s, [f.result() for f in futures[s.session_id]])
        for s in sessions
    }


# -- the always-on seeded sweep ----------------------------------------------
@pytest.fixture(scope="module")
def seeded_case():
    wc = _workload(seed=23)
    sessions = _generate(wc)
    return sessions, _sequential_reference(sessions)


@pytest.mark.parametrize("workers,shared_tier", FLEET_SHAPES)
def test_fleet_byte_identical_to_sequential(
    seeded_case, tmp_path, workers, shared_tier
):
    sessions, reference = seeded_case
    got = _fleet_run(sessions, workers, shared_tier, tmp_path / "tier")
    assert set(got) == set(reference)
    for sid in reference:
        assert got[sid] == reference[sid], f"divergence in session {sid}"


def test_fleet_refuses_the_jax_plane():
    with pytest.raises(ValueError, match="one process"):
        VerificationFleet(2, config=CONFIG.replace(plane="jax"))


def test_second_seed_with_larger_fleet_than_clients(tmp_path):
    """More workers than clients: some shards idle, answers unchanged."""
    wc = _workload(seed=77, sessions=2, chain_length=4)
    sessions = _generate(wc)
    reference = _sequential_reference(sessions)
    got = _fleet_run(sessions, workers=4, shared_tier="remote",
                     tier_dir=tmp_path / "tier")
    assert got == reference


def test_warm_remote_tier_changes_no_answers(tmp_path):
    """A second fleet over the SAME remote tier serves pair/verdict hits
    (after certificate replay) — and still answers byte-identically.

    Work accounting is the one legitimate difference: a tier-served pair
    never ran its search, so ``decompositions_explored`` reports the
    avoided work (0), exactly like an intra-process pair-cache hit.  The
    *answers* — verdicts, certificates, sink bytes — must not move."""
    wc = _workload(seed=5, sessions=2, chain_length=4)
    sessions = _generate(wc)
    reference = _sequential_reference(sessions)
    cold = _fleet_run(sessions, 2, "remote", tmp_path / "tier")
    warm = _fleet_run(sessions, 2, "remote", tmp_path / "tier")
    assert cold == reference

    def answers(trace):
        return [
            t if t[0] in ("none", "first") else (t[0], *t[2:]) for t in trace
        ]

    assert {s: answers(t) for s, t in warm.items()} == {
        s: answers(t) for s, t in reference.items()
    }


# -- the hypothesis-widened property -----------------------------------------
if HAS_HYPOTHESIS:

    @settings(max_examples=4, deadline=None)
    @given(
        seed=st.integers(min_value=0, max_value=2**16),
        workers=st.sampled_from([1, 2, 4]),
        shared_tier=st.sampled_from(["local", "remote"]),
    )
    def test_property_fleet_equals_reference(seed, workers, shared_tier):
        wc = _workload(seed=seed, sessions=2, chain_length=4)
        sessions = _generate(wc)
        reference = _sequential_reference(sessions)
        tier_dir = tempfile.mkdtemp(prefix="veer-difftier-")
        try:
            got = _fleet_run(sessions, workers, shared_tier, tier_dir)
        finally:
            shutil.rmtree(tier_dir, ignore_errors=True)
        assert got == reference

else:  # pragma: no cover - exercised on minimal installs

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_fleet_equals_reference():
        pass
