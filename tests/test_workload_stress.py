"""Workload generator + sustained-traffic differential stress suite (ISSUE 6).

Tier-1 twin of ``benchmarks/session_bench.py``: small seeds, small chains,
seconds-fast, every differential oracle on.  Covers the determinism
contract (same seed ⇒ byte-identical sessions), the five edit families'
construction guarantees, the replay driver's oracles, ``ServiceBusy``
backpressure and abandoned tickets under generated burst traffic, and the
labeled-window corpus round-trip.
"""

import io
import json
import threading

import pytest

from repro.api import VeerConfig
from repro.core import dag as D
from repro.service import ServiceBusy, VerificationService, VersionChainSession
from repro.workload import (
    EXPECTED_EQ,
    SessionGenerator,
    WindowExample,
    WorkloadConfig,
    WorkloadConfigError,
    canonical_sink_bytes,
    dump_windows,
    load_windows,
    replay_sessions,
    windows_from_certificate,
)
from repro.workload.replay import canonical_results_bytes

# small + fast: two light shapes, short chains, tight search budget (the
# semantic family's UNK searches are EV-call-bound, so the budget is the
# knob that keeps this suite in seconds)
FAST = WorkloadConfig(
    seed=7, sessions=3, clients=3, chain_length=6,
    workloads=("W1", "W5", "W8"), rows=12, max_decompositions=60,
)


def _exec_bytes(session, idx):
    dag = session.versions[idx]
    from repro.engine.executor import execute

    srcs = {k: v for k, v in session.sources.items() if k in dag.ops}
    return canonical_results_bytes(dag, execute(dag, srcs))


# ---------------------------------------------------------------------------
# WorkloadConfig: validation + serialization (VeerConfig-style)
# ---------------------------------------------------------------------------


def test_config_roundtrips_and_defaults_validate():
    cfg = FAST.validate()
    again = WorkloadConfig.from_json(cfg.to_json())
    assert again == cfg
    assert again.to_json() == cfg.to_json()
    assert WorkloadConfig().validate().total_pairs > 0


@pytest.mark.parametrize(
    "changes",
    [
        {"sessions": 0},
        {"chain_length": 1},
        {"qps": -1.0},
        {"workloads": ()},
        {"workloads": ("W1", "W99")},
        {"edit_mix": ()},
        {"edit_mix": (("nope", 1.0),)},
        {"edit_mix": (("equivalent", 1.0), ("equivalent", 2.0))},
        {"edit_mix": (("equivalent", 0.0),)},
        {"rows": -3},
        {"max_decompositions": 0},
    ],
)
def test_config_rejects_bad_values(changes):
    with pytest.raises(WorkloadConfigError):
        WorkloadConfig(**changes).validate()


def test_config_rejects_unknown_fields():
    with pytest.raises(WorkloadConfigError):
        WorkloadConfig.from_dict({"sessions": 2, "not_a_field": 1})


# ---------------------------------------------------------------------------
# determinism: same seed => byte-identical sessions (satellite 2)
# ---------------------------------------------------------------------------


def test_same_seed_generates_byte_identical_sessions():
    a = SessionGenerator(FAST).generate()
    b = SessionGenerator(FAST).generate()
    assert [s.signature() for s in a] == [s.signature() for s in b]
    # sessions are independently addressable and order-independent
    assert SessionGenerator(FAST).session(1).signature() == a[1].signature()


def test_different_seeds_generate_different_sessions():
    a = SessionGenerator(FAST).generate()
    b = SessionGenerator(FAST.replace(seed=FAST.seed + 1)).generate()
    assert [s.signature() for s in a] != [s.signature() for s in b]


def test_edit_generators_are_seed_deterministic():
    """The threaded-rng contract of repro.workload.flows: same explicit
    seed ⇒ byte-identical edited DAG, no module-level random state."""
    import random

    from repro.workload.flows import (
        apply_equivalent_edits,
        apply_inequivalent_edits,
        build_workloads,
    )
    from repro.api.serialize import dag_to_dict

    P = build_workloads()["W5"]
    for fn in (apply_equivalent_edits, apply_inequivalent_edits):
        random.seed(12345)  # poisoning global state must not matter
        q1 = json.dumps(dag_to_dict(fn(P, 3, seed=9)), sort_keys=True)
        random.seed(999)
        q2 = json.dumps(dag_to_dict(fn(P, 3, seed=9)), sort_keys=True)
        assert q1 == q2


# ---------------------------------------------------------------------------
# session construction guarantees per family
# ---------------------------------------------------------------------------


def test_sessions_have_planned_shape():
    for s in SessionGenerator(FAST).generate():
        assert len(s.versions) == FAST.chain_length
        assert len(s.pairs) == FAST.chain_length - 1
        for v in s.versions:
            v.validate()
        assert set(s.sources) == set(s.versions[0].sources)


def test_expected_eq_pairs_are_execution_equal():
    """Equivalence-by-construction families must be *actually* equivalent
    on the session's source bindings — this audits the generator itself,
    independent of the verifier."""
    for s in SessionGenerator(FAST).generate():
        for p in s.pairs:
            if p.expected == EXPECTED_EQ:
                assert _exec_bytes(s, p.index - 1) == _exec_bytes(s, p.index), (
                    f"{s.session_id} pair {p.index} ({p.kind}) not "
                    f"execution-equal"
                )


def test_rename_storm_preserves_sources_sinks_and_content():
    cfg = FAST.replace(edit_mix=(("rename_storm", 1.0),), chain_length=3)
    s = SessionGenerator(cfg).session(0)
    P, Q = s.versions[0], s.versions[1]
    planned = s.pairs[0]
    assert planned.kind == "rename_storm" and planned.mapping is not None
    # interior ids all renamed; SOURCE/SINK ids stable
    for pid, qid in planned.mapping.forward.items():
        if P.ops[pid].op_type in (D.SOURCE, D.SINK):
            assert pid == qid
        else:
            assert pid != qid
    assert set(P.sources) == set(Q.sources)
    assert set(P.sinks) == set(Q.sinks)
    # with the explicit mapping the pair is zero-change: verdict True with
    # a certificate that replays green *bound to the pair*
    from repro.api import verify

    res = verify(P, Q, VeerConfig(evs=("equitas", "spes", "udp")),
                 mapping=planned.mapping)
    assert res.verdict is True
    assert res.certificate is not None
    assert res.certificate.replay(None, P, Q).ok


def test_churn_revert_rehits_pair_cache():
    cfg = FAST.replace(edit_mix=(("churn_revert", 1.0),), chain_length=8,
                       sessions=2, clients=2)
    sessions = SessionGenerator(cfg).generate()
    result = replay_sessions(sessions, cfg)
    assert result.ok, result.summary()
    # every completed A->B / B->A / A->B cycle re-hits the shared pair
    # cache on its third pair (identical re-applied edit, identical ids)
    assert result.reused >= len(sessions)
    assert result.pair_cache_stats["hits"] == result.reused


# ---------------------------------------------------------------------------
# replay driver: oracles + determinism of the whole pipeline
# ---------------------------------------------------------------------------


def test_replay_all_families_zero_violations():
    sessions = SessionGenerator(FAST).generate()
    result = replay_sessions(sessions, FAST, collect_windows=True)
    assert result.ok, result.summary()
    assert result.pairs == FAST.total_pairs
    assert result.verdicts["EQ"] >= 1
    # every decided pair carried a certificate (checked replay-green by the
    # oracle); UNK pairs carry none
    assert result.certified == result.decided
    assert result.p99_latency >= result.p50_latency >= 0.0


def test_replay_is_deterministic_under_a_fixed_seed():
    """Same config ⇒ same verdict census and byte-identical harvested
    windows, regardless of service thread interleaving."""
    r1 = replay_sessions(SessionGenerator(FAST).generate(), FAST,
                         collect_windows=True)
    r2 = replay_sessions(SessionGenerator(FAST).generate(), FAST,
                         collect_windows=True)
    assert r1.ok and r2.ok
    assert r1.verdicts == r2.verdicts
    assert [w.to_dict() for w in r1.windows] == [w.to_dict() for w in r2.windows]


def test_replay_with_exec_reuse_is_bit_identical():
    cfg = FAST.replace(sessions=2, clients=2)
    result = replay_sessions(SessionGenerator(cfg).generate(), cfg,
                             exec_reuse=True)
    assert result.ok, result.summary()
    assert result.pairs == cfg.total_pairs


def test_canonical_sink_bytes_semantics():
    from repro.engine.table import Table

    t1 = Table.from_rows(("a", "b"), [(1, 2), (3, 4)])
    t2 = Table.from_rows(("a", "b"), [(3, 4), (1, 2)])
    assert canonical_sink_bytes(t1, D.BAG) == canonical_sink_bytes(t2, D.BAG)
    assert canonical_sink_bytes(t1, D.ORDERED) != canonical_sink_bytes(t2, D.ORDERED)
    dup = Table.from_rows(("a", "b"), [(1, 2), (1, 2), (3, 4)])
    assert canonical_sink_bytes(dup, D.SET) == canonical_sink_bytes(t1, D.SET)
    assert canonical_sink_bytes(dup, D.BAG) != canonical_sink_bytes(t1, D.BAG)


# ---------------------------------------------------------------------------
# ServiceBusy backpressure + abandoned tickets under burst traffic (sat. 3)
# ---------------------------------------------------------------------------

SVC_CONFIG = VeerConfig(evs=("equitas", "spes", "udp"), max_decompositions=60)


def test_generated_burst_traffic_hits_backpressure_and_recovers():
    """A generated session fired at a tiny saturated queue must raise
    ``ServiceBusy`` (not block, not buffer); the chain then continues with
    the accepted versions only, and drain reports exactly those pairs."""
    session = SessionGenerator(FAST.replace(chain_length=10)).session(0)
    gate = threading.Event()
    svc = VerificationService(config=SVC_CONFIG, workers=1, queue_size=1)
    accepted = []
    rejected = 0
    try:
        from concurrent.futures import Future

        from repro.service.server import _Job

        # wedge the only worker so queue occupancy is deterministic
        blocker = _Job(client=None, ticket=0, fn=lambda: gate.wait(30),
                       future=Future())
        with svc._lock:
            svc._pending += 1
        svc._queue.put(blocker)
        # first version submitted blocking: it is guaranteed queued (the
        # wedged worker consumes only the blocker), making queue occupancy
        # deterministic for the burst below
        svc.submit("burst", session.versions[0])
        accepted.append(session.versions[0])
        for v in session.versions[1:]:
            try:
                svc.submit("burst", v, block=False)
                accepted.append(v)
            except ServiceBusy:
                rejected += 1
        assert rejected > 0, "burst never saturated the queue"
        gate.set()
        # abandoned tickets must not wedge later jobs: submit the rejected
        # tail again, blocking this time
        tail = session.versions[len(accepted):]
        for v in tail:
            svc.submit("burst", v)
            accepted.append(v)
        report = svc.drain()
        assert report.errors == []
        assert len(report.sessions["burst"].pairs) == len(accepted) - 1
        # drain-after-burst consistency: the surviving chain's verdicts are
        # exactly a sequential replay of the accepted versions
        with VersionChainSession(config=SVC_CONFIG) as seq:
            for v in accepted:
                seq.submit(v)
        assert report.sessions["burst"].verdicts == seq.report().verdicts
        # drain is repeatable and stays consistent after the burst
        again = svc.drain()
        assert again.sessions["burst"].verdicts == report.sessions["burst"].verdicts
        assert again.errors == []
    finally:
        gate.set()
        svc.close(save=False)


def test_replay_driver_counts_busy_and_drops_no_version():
    """The driver submits with block=False first: with a tiny queue it must
    record rejections, resubmit blocking, and still verify every pair."""
    cfg = FAST.replace(sessions=2, clients=2)
    sessions = SessionGenerator(cfg).generate()
    result = replay_sessions(sessions, cfg, workers=1, queue_size=1)
    assert result.ok, result.summary()
    assert result.pairs == cfg.total_pairs  # no version was dropped
    assert result.busy_rejections > 0


# ---------------------------------------------------------------------------
# labeled-window corpus (satellite 6)
# ---------------------------------------------------------------------------


def test_window_corpus_schema_roundtrip():
    sessions = SessionGenerator(FAST).generate()
    result = replay_sessions(sessions, FAST, collect_windows=True)
    assert result.ok and result.windows, "replay harvested no windows"
    buf = io.StringIO()
    report = dump_windows(result.windows, buf, dedupe=False)
    assert report.written == len(result.windows)
    assert report.dropped_duplicates == 0
    buf.seek(0)
    loaded = list(load_windows(buf))
    assert loaded == list(result.windows)
    # the default path dedupes by fingerprint and reports per-label counts
    buf2 = io.StringIO()
    deduped = dump_windows(result.windows, buf2)
    assert deduped.written + deduped.dropped_duplicates == len(result.windows)
    assert sum(deduped.label_counts.values()) == deduped.written
    buf2.seek(0)
    assert len(list(load_windows(buf2))) == deduped.written
    # each line is standalone JSON with the full schema
    first = json.loads(buf.getvalue().splitlines()[0])
    for key in ("fingerprint", "op_hist", "topology", "verdict", "workload",
                "ev_name", "family", "record_kind"):
        assert key in first
    # features are populated on ev-decided windows
    ev_windows = [w for w in result.windows if w.record_kind == "ev"]
    assert ev_windows
    for w in ev_windows:
        assert w.fingerprint and w.op_hist and w.topology["p_ops"] > 0


def test_windows_from_certificate_features():
    from repro.api import verify
    from repro.workload.generator import SessionGenerator as SG

    s = SG(FAST).session(0)
    eq_pairs = [p for p in s.pairs if p.expected == EXPECTED_EQ]
    p = eq_pairs[0]
    res = verify(s.versions[p.index - 1], s.versions[p.index], SVC_CONFIG,
                 mapping=p.mapping)
    assert res.certificate is not None
    examples = windows_from_certificate(
        res.certificate, workload=s.workload, session_id=s.session_id,
        pair_index=p.index, family=p.kind, expected=p.expected,
    )
    assert len(examples) == len(res.certificate.windows)
    for ex, rec in zip(examples, res.certificate.windows):
        assert ex.verdict == rec.verdict
        assert ex.fingerprint == rec.fingerprint
        assert ex.cert_kind == res.certificate.kind
        assert WindowExample.from_dict(ex.to_dict()) == ex
