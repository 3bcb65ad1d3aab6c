"""The program's spans (``repro.obs``): free when no trace records, and,
under ``jax.profiler.start_trace``, one span per operator, no two
``veer.`` spans open at once on a thread (but the join phases inside
``veer.exec.Join`` and the EV calls inside ``veer.search.decide``), one
request id per job on every span of that job, and the queue wait on
every pickup."""

import glob
import sys
import time
from collections import Counter, defaultdict

import numpy as np
import pytest

from repro import obs
from repro.core import dag as D
from repro.core.dag import DataflowDAG, Link, Operator
from repro.core.predicates import Pred
from repro.engine.table import Table


def test_span_is_free_without_a_recording_profiler():
    import jax.profiler  # noqa: F401 - the span's TraceAnnotation path

    def batch(n=4000):
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("veer.test", nl=1) as sp:
                sp.set_metadata(device=0)
        return (time.perf_counter() - t0) / n

    per_call = min(batch() for _ in range(5))  # the least disturbed batch
    assert per_call < 5e-6, per_call


def test_span_is_a_noop_where_jax_was_never_loaded(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax.profiler")
    with obs.request("c:0"), obs.span("veer.test", nl=1) as sp:
        sp.set_metadata(device=1)
    assert sp is obs.span("veer.other")


def test_request_ids_nest_and_restore():
    assert getattr(obs._local, "req", None) is None
    with obs.request("a:0"):
        with obs.request("b:1"):
            assert obs._local.req == "b:1"
        assert obs._local.req == "a:0"
    assert getattr(obs._local, "req", None) is None


def _dag(limit):
    """Filter the left side, join on three sparse keys (the device
    probe), filter the join."""
    ops = [
        Operator.make("l", D.SOURCE, schema=("k0", "k1", "k2", "x")),
        Operator.make("r", D.SOURCE, schema=("k0", "k1", "k2", "y")),
        Operator.make("f", D.FILTER, pred=Pred.cmp("x", "<=", limit)),
        Operator.make("j", D.JOIN, on=(("k0", "k0"), ("k1", "k1"), ("k2", "k2")),
                      how="left_outer"),
        Operator.make("g", D.FILTER, pred=Pred.cmp("y", ">=", 1)),
        Operator.make("sink", D.SINK, semantics=D.BAG),
    ]
    links = [Link("l", "f"), Link("f", "j", 0), Link("r", "j", 1),
             Link("j", "g"), Link("g", "sink")]
    return DataflowDAG(ops, links)


def _sources(n=300):
    rng = np.random.default_rng(5)

    def side(val):
        cols = {f"k{i}": rng.integers(0, 1 << 40, n).astype(np.float64) for i in range(3)}
        for i in range(3):  # a quarter of the rows match across the sides
            cols[f"k{i}"][: n // 4] = np.arange(n // 4, dtype=np.float64)
        cols[val] = rng.integers(0, 9, n).astype(np.float64)
        return Table(cols, ["k0", "k1", "k2", val])

    return {"l": side("x"), "r": side("y")}


def _events(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("veer."):
                    out.append((f"{plane.name}#{i}", ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two clients' chains and one one-shot pair on a two-worker service
    over the jax plane, under a profiler trace; the spans recorded and the
    plane's ``execute_op`` calls counted by operator type."""
    import jax

    from repro.api import VeerConfig
    from repro.engine import InMemoryMaterializationStore
    from repro.engine.plane.jax_plane import JaxPlane
    from repro.service import VerificationService

    calls = Counter()
    inner = JaxPlane.execute_op

    def counting(self, op, inputs):
        calls[op.op_type] += 1
        return inner(self, op, inputs)

    sources = _sources()
    limits = {"a": (5, 6, 4), "b": (3, 3, 7)}
    log_dir = str(tmp_path_factory.mktemp("trace"))
    svc = VerificationService(
        config=VeerConfig(plane="jax", exec_mode="delta"),
        materialization_store=InMemoryMaterializationStore(), workers=2)
    JaxPlane.execute_op = counting
    try:
        jax.profiler.start_trace(log_dir)
        try:
            futures = [svc.submit(client, _dag(lim[k]), sources=sources)
                       for k in range(3) for client, lim in limits.items()]
            futures.append(svc.submit_pair(_dag(5), _dag(6)))
            for fut in futures:
                fut.result(timeout=120)
        finally:
            jax.profiler.stop_trace()
    finally:
        JaxPlane.execute_op = inner
        svc.close(save=False)
    jobs = {f"{c}:{k}" for c in limits for k in range(3)} | {"pair:0"}
    return _events(log_dir), calls, jobs


def test_one_exec_span_per_execute_op_call(traced):
    spans, calls, _ = traced
    seen = Counter(name[len("veer.exec."):] for _, name, *_ in spans
                   if name.startswith("veer.exec.") and name != "veer.exec.frontier")
    assert calls["Join"] > 0 and calls["Filter"] > 0
    assert seen == calls
    names = {name for _, name, *_ in spans}
    assert {"veer.service.dequeue", "veer.chain.plan", "veer.search.decide",
            "veer.exec.frontier", "veer.store.put", "veer.plane.join.codes",
            "veer.plane.join.argsort", "veer.plane.join.probe",
            "veer.plane.join.expand"} <= names


def test_no_two_spans_open_on_a_thread_but_the_join_phases(traced):
    spans, _, _ = traced
    by_thread = defaultdict(list)
    for thread, name, start, end, _ in spans:
        by_thread[thread].append((start, end, name))
    inside = {"veer.exec.Join": "veer.plane.join.", "veer.search.decide": "veer.ev.check"}
    for items in by_thread.values():
        items.sort()
        outer = None  # (start, end, prefix of the phases it may hold)
        last_end = -1
        for start, end, name in items:
            if outer is not None and name.startswith(outer[2]):
                assert outer[0] <= start and end <= outer[1], (name, start, outer)
                continue
            assert not name.startswith(tuple(inside.values())), name
            assert start >= last_end, (name, start, last_end)
            last_end = end
            outer = (start, end, inside[name]) if name in inside else None


def test_every_span_of_a_job_carries_its_request_id(traced):
    spans, _, jobs = traced
    by_req = defaultdict(list)
    for thread, name, start, end, stats in spans:
        assert "req" in stats, name
        by_req[stats["req"]].append((thread, name, start, end))
    assert set(by_req) == jobs
    for req, items in by_req.items():
        assert len({thread for thread, *_ in items}) == 1, req  # a job runs on one worker
        dequeues = [start for _, name, start, _ in items if name == "veer.service.dequeue"]
        assert len(dequeues) == 1 and dequeues[0] == min(s for *_, s, _ in items), req
    # a chain job plans its version; a one-shot pair does not
    assert all(any(n == "veer.chain.plan" for _, n, *_ in by_req[r]) for r in jobs - {"pair:0"})
    decide = [stats for _, name, *_, stats in spans if name == "veer.search.decide"]
    assert len(decide) == 4
    assert all(s["verdict"] in ("eq", "neq", "unk") and s["reused"] in (0, 1) for s in decide)


def test_every_dequeue_carries_its_queue_wait(traced):
    spans, _, jobs = traced
    dequeues = [stats for _, name, *_, stats in spans if name == "veer.service.dequeue"]
    assert len(dequeues) == len(jobs)
    assert all(isinstance(s["queued_s"], float) and 0 <= s["queued_s"] < 120 for s in dequeues)
    probes = [stats for _, name, *_, stats in spans if name == "veer.plane.join.probe"]
    assert probes and all(s["bucket_l"] >= s["nl"] and s["bucket_r"] >= s["nr"] for s in probes)


def test_decide_spans_count_their_search_and_each_ev_call_has_a_span(traced):
    spans, _, _ = traced
    decide = [stats for _, name, *_, stats in spans if name == "veer.search.decide"]
    for s in decide:
        assert all(s[k] >= 0 for k in ("decompositions", "ev_calls", "sat_calls")), s
    checks = [stats for _, name, *_, stats in spans if name == "veer.ev.check"]
    assert checks and len(checks) == sum(s["ev_calls"] for s in decide)
    assert sum(s["sat_calls"] for s in decide) > 0
    for s in checks:
        assert s["ev"] in ("equitas", "spes", "udp", "jaxpr") and s["ops"] >= 2, s
        assert s["verdict"] in ("eq", "neq", "unk"), s
