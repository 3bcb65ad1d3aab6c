"""One run of one cell: set-up, the closed-loop window, the check.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); metrics are read by the
files ``bench/metrics/<name>.py``, each with ``read(run) -> float | None``.
Nothing here knows a cell, a mix or a metric by name.

The run:

1. source tables from the seed (``bench.datagen``);
2. one ``VerificationService`` (the service's default 4 workers) over an
   ``InMemoryMaterializationStore`` with the configuration's byte budget,
   config ``VeerConfig(plane="jax", exec_mode="delta",
   evs=("equitas", "spes", "udp"))``;
3. warm-up: every analyst submits its first session's base version (a full
   execution that fills the store); then every device-probed join of the
   base dataflow runs once per power-of-two bucket its left input can
   take, so no probe compiles inside the window;
4. the window: each analyst submits its next version as soon as the
   previous one has its verdict and sinks, for ``seconds``; when a session
   ends, the next opens from the base under a new client id, so its first
   version is a full execution;
5. the versions still in flight at the close are waited for (their
   latencies count), the device's peak memory read, the service closed and
   its store dropped;
6. the check: a sample of the versions submitted in the window, drawn from
   the seed, with the slowest in it, against the plain reference
   (``bench.reference``).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import queue
import random
import resource
import shutil
import sys
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import check, datagen, dataflow, reference
from bench import trace as tr

BENCH = pathlib.Path(__file__).resolve().parent
SPAN_PREFIX = "veer."
WINDOW_SPAN = "bench.window"


def _log(t0: float, msg: str) -> None:
    print(f"[{time.perf_counter() - t0:8.2f}s] {msg}", file=sys.stderr, flush=True)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


# every key a traffic mix may have; a mix is a closed loop of analysts
MIX_KEYS = frozenset({"why", "analysts", "session_seed", "versions_per_session",
                      "max_edits_per_version", "mix", "check_sample"})


def load_cell(root: pathlib.Path, name: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    (w,) = [w for w in spec["workloads"] if w["name"] == name]
    (c,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    config = json.loads((root / c["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    unread = set(traffic) - MIX_KEYS
    if unread:
        raise ValueError(f"traffic mix {w['traffic']!r} has keys the harness does not "
                         f"read: {sorted(unread)}")

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(name, config, traffic, int(w["chips"]),
                [m for m in spec["end_to_end"] if applies(m)],
                [m for m in spec["per_layer"] if applies(m)])


def load_reader(metric: str) -> Callable:
    path = BENCH / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# -- what one run gathers ---------------------------------------------------------
@dataclass
class Answer:
    """One submitted version and what the service answered."""

    index: int
    expected_eq: bool
    flow: dataflow.Flow
    prev_flow: Optional[dataflow.Flow]
    t_submit: float
    t_done: Optional[float] = None
    in_window: bool = False
    error: Optional[str] = None
    verdict: Optional[bool] = None
    certified: bool = False
    wall_time_s: float = 0.0
    verify_s: Optional[float] = None
    exec_s: float = 0.0
    ops_executed: int = 0
    ops_total: int = 0
    ops_on_device: int = 0
    sinks: Dict[str, tuple] = field(default_factory=dict)

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit

    @property
    def is_pair(self) -> bool:
        return self.index > 0


@dataclass
class Run:
    """Everything the metric readers see."""

    seconds: float
    setup_s: float
    answers: List[Answer]
    failed: int
    trace: Optional[dict] = None
    probe_calls: List[tuple] = field(default_factory=list)
    peaks: Optional[dict] = None

    @property
    def window_answers(self) -> List[Answer]:
        """Versions answered before the window closed."""
        return [a for a in self.answers if a.in_window and a.error is None]

    @property
    def submitted(self) -> List[Answer]:
        """Every version submitted in the window and answered, those
        answered after the close included."""
        return [a for a in self.answers if a.error is None]


# -- sessions ------------------------------------------------------------------
class Sessions:
    """Each analyst's sessions.

    The traffic mix fixes analyst ``a``'s sessions, grown lazily from
    ``(session_seed, a, session)``, so every seed asks for the same edits
    (on its own data) and runs differ by the data and the interleaving,
    not by how much work the edits make.  Every analyst starts at its
    first session's base version.
    """

    def __init__(self, config: dict, traffic: dict):
        self.base = dataflow.Flow.from_json(config["dataflow"])
        self.domains = config.get("domains", {})
        self.traffic = traffic

    def session(self, analyst: int, number: int) -> List[tuple]:
        key = f"{self.traffic['session_seed']}:{analyst}:{number}".encode()
        rng = random.Random(zlib.crc32(key))
        versions = dataflow.make_session(
            self.base, self.traffic["mix"], int(self.traffic["versions_per_session"]),
            int(self.traffic["max_edits_per_version"]), self.domains, rng,
            tag=f"t{analyst}s{number}")
        return [(v, dataflow.to_dag(v.flow), dataflow.to_mapping(v.mapping))
                for v in versions]


def _program_tables(config: dict, tables: Dict[str, dict]):
    """Each source as the program reads it: the columns its schema names,
    out of the table at full width (a columnar scan)."""
    from repro.engine.table import Table

    base = dataflow.Flow.from_json(config["dataflow"])
    return {sid: Table({c: tables[sid][c] for c in base.ops[sid]["schema"]},
                       list(base.ops[sid]["schema"]))
            for sid in tables}


def _device_check(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoAccelerator(
            f"need {chips} TPU chip(s); JAX sees {len(devs)} {devs[0].platform} device(s)")
    return devs


def _compile_cache(root: pathlib.Path) -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _warm_probe_buckets(config: dict, sources) -> int:
    """Run every multi-key join of the base dataflow that reaches the device
    at full size once per power-of-two bucket of its left input, so that
    each probe shape compiles (or loads from the cache) before the window.
    Returns how many joins were warmed."""
    from repro.engine.executor import ExecutionPlan
    from repro.engine.plane import get_plane

    base = dataflow.Flow.from_json(config["dataflow"])
    dag = dataflow.to_dag(base)
    plane = get_plane("jax")
    joins = [i for i in base.topo_order()
             if base.ops[i]["op"] == "join" and len(base.ops[i]["on"]) > 1]
    if not joins:
        return 0
    inputs = sorted({s for j in joins for s, _, _ in base.in_links(j)})
    tables = ExecutionPlan(dag, sources, plane="jax").run(keep=inputs).results
    warmed = 0
    for j in joins:
        op = dag.ops[j]
        left, right = (tables[s] for s, _, _ in base.in_links(j))
        before = plane.device_dispatches()
        plane.execute_op(op, [left, right])
        if plane.device_dispatches() == before:
            continue
        warmed += 1
        for k in range(max(1, len(left) - 1).bit_length()):
            n = 1 if k == 0 else (1 << (k - 1)) + 1
            plane.execute_op(op, [left.take(np.arange(n)), right])
    return warmed


class _Instrument:
    """Host spans around each operator the plane executes, and the logical
    rows of every join that dispatched to the device (trace runs only)."""

    def __init__(self):
        self.recording = False
        self.probe_calls: List[tuple] = []
        self._lock = threading.Lock()

    def install(self, plane):
        import jax

        inner = plane.execute_op

        def execute_op(op, inputs):
            before = plane.device_dispatches()
            with jax.profiler.TraceAnnotation(f"{SPAN_PREFIX}exec.{op.op_type}"):
                out = inner(op, inputs)
            if (self.recording and op.op_type == "Join"
                    and plane.device_dispatches() > before):
                with self._lock:
                    self.probe_calls.append((len(inputs[0]), len(inputs[1])))
            return out

        plane.execute_op = execute_op
        return lambda: delattr(plane, "execute_op")


def _answer_from(a: Answer, fut) -> None:
    exc = fut.exception()
    if exc is not None:
        a.error = repr(exc)
        return
    rep = fut.result()
    a.verdict = rep.verdict
    a.certified = bool(rep.certified)
    a.wall_time_s = float(rep.wall_time)
    if a.is_pair:
        a.verify_s = float(rep.stats.total_time)
    e = rep.exec_stats
    a.exec_s = float(e.wall_time)
    a.ops_executed, a.ops_total, a.ops_on_device = e.ops_executed, e.ops_total, e.ops_on_device
    a.sinks = {sid: (list(t.order), dict(t.cols)) for sid, t in rep.results.items()}


class ClosedLoop:
    """Each analyst submits its next version as soon as the previous one is
    answered.  ``window`` returns at the close, leaving the versions in
    flight; ``drain`` waits for them (they are answered late)."""

    def __init__(self, svc, sessions: Sessions, analysts: int, sources,
                 first: Dict[int, list]):
        self.svc, self.sessions, self.sources = svc, sessions, sources
        self.answers: List[Answer] = []
        self.refused = 0
        self.in_flight = 0
        self._done: "queue.Queue" = queue.Queue()
        self._plan = {a: {"session": 0, "index": 1, "versions": first[a], "next": None}
                      for a in range(analysts)}
        self._t_end = 0.0

    def _submit(self, a: int) -> None:
        st = self._plan[a]
        if st["index"] == len(st["versions"]):
            st["session"] += 1
            st["index"] = 0
            st["versions"] = st["next"] or self.sessions.session(a, st["session"])
            st["next"] = None
        k = st["index"]
        st["index"] += 1
        version, dag, mapping = st["versions"][k]
        prev = st["versions"][k - 1][0].flow if k else None
        ans = Answer(k, version.expected_eq, version.flow, prev,
                     t_submit=time.perf_counter())
        self.answers.append(ans)
        self.in_flight += 1
        try:
            fut = self.svc.submit(f"a{a}s{st['session']}", dag, mapping,
                                  sources=self.sources)
        except Exception as exc:  # noqa: BLE001 - a refused submit is counted
            ans.error, ans.t_done = repr(exc), time.perf_counter()
            self.refused += 1
            self._done.put((a, ans, None))
            return

        def on_done(f, a=a, ans=ans):
            ans.t_done = time.perf_counter()
            self._done.put((a, ans, f))

        fut.add_done_callback(on_done)
        if st["next"] is None:
            st["next"] = self.sessions.session(a, st["session"] + 1)

    def _settle(self, ans: Answer, fut) -> None:
        if fut is not None:
            _answer_from(ans, fut)
        ans.in_window = ans.t_done <= self._t_end
        self.in_flight -= 1

    def window(self, seconds: float) -> None:
        self._t_end = time.perf_counter() + seconds
        for a in self._plan:
            self._submit(a)
        while True:
            left = self._t_end - time.perf_counter()
            if left <= 0:
                return
            try:
                a, ans, fut = self._done.get(timeout=left)
            except queue.Empty:
                return
            self._settle(ans, fut)
            if time.perf_counter() < self._t_end:
                self._submit(a)

    def drain(self, timeout_s: float = 300.0) -> None:
        while self.in_flight:
            _, ans, fut = self._done.get(timeout=timeout_s)
            self._settle(ans, fut)


def _sample(answers: List[Answer], seed: int, k: int) -> List[Answer]:
    ok = [a for a in answers if a.error is None]
    if not ok:
        return []
    rng = random.Random(seed ^ 0x5A5A5A5A)
    picked = rng.sample(ok, min(k, len(ok)))
    slowest = max(ok, key=lambda a: a.latency_s)
    if slowest not in picked:
        picked.append(slowest)
    return picked


def check_answers(answers: List[Answer], tables: Dict[str, dict], seed: int,
                  sample_size: int, gap_limit: float) -> Dict[str, float]:
    """The numbers ``correct`` compares (see ``bench/check.py``).  An EQ
    verdict is wrong where the reference's answers for its two versions
    differ by more than ``gap_limit``, the limit of rounding."""
    readings = {
        "failed": float(sum(a.error is not None for a in answers)),
        "neq_on_eq_edit": float(sum(a.expected_eq and a.verdict is False
                                    for a in answers if a.error is None)),
        "uncertified_verdict": float(sum(a.verdict is not None and not a.certified
                                         for a in answers if a.error is None)),
        "sink_gap": 0.0,
        "eq_claim_wrong": 0.0,
    }
    memo: Dict[int, dict] = {}

    def ref(flow):
        key = id(flow)
        if key not in memo:
            memo[key] = (flow, reference.run(flow, tables))
        return memo[key][1]

    for a in _sample(answers, seed, sample_size):
        want = ref(a.flow)
        for sid, served in a.sinks.items():
            sem = a.flow.ops[sid].get("semantics", "bag")
            readings["sink_gap"] = max(readings["sink_gap"],
                                       check.sink_gap(served, want[sid], sem))
        if a.verdict is True and a.prev_flow is not None:
            before = ref(a.prev_flow)
            for sid in want:
                sem = a.flow.ops[sid].get("semantics", "bag")
                if check.sink_gap(want[sid], before[sid], sem) > gap_limit:
                    readings["eq_claim_wrong"] += 1
                    break
    return readings


def control_answers(answers: List[Answer], tables: Dict[str, dict], seed: int,
                    sample_size: int) -> List[Answer]:
    """The control: ``answers`` with the plain reference computed in
    float32 put in the program's place, as the served sinks of every answer
    that ``check_answers`` samples (the same ones, by the same seed)."""
    picked = {id(a) for a in _sample(answers, seed, sample_size)}
    out = []
    for a in answers:
        if id(a) in picked:
            low = reference.run(a.flow, tables, dtype=np.float32)
            a = dataclasses.replace(a, sinks={sid: low[sid] for sid in a.sinks})
        out.append(a)
    return out


def _reduce_trace(log_dir: str) -> dict:
    events = tr.load(tr.find_xplane(log_dir))
    window = tr.span_window(events, WINDOW_SPAN)
    busy = tr.busy_s(events, window)
    probe_s, _ = tr.module_s(events, "jit__join_probe_body", window)
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy,
        "probe_s": probe_s,
        "top_ops": tr.top_ops(events, window),
        "idle_gaps": tr.idle_gaps(events, window, SPAN_PREFIX),
    }


@dataclass
class Outcome:
    """The result line's object, the compared numbers, host facts, the run,
    and with ``control`` the control's compared numbers and ``correct``."""

    line: dict
    readings: Dict[str, float]
    host: dict
    run: Run
    control: Optional[dict] = None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, root: pathlib.Path, require_tpu: bool = True,
             control: bool = False) -> Outcome:
    """One run of ``cell``.  ``require_tpu=False`` skips the look for a chip
    (the CPU tests); ``control`` also judges the control
    (``control_answers``) by the same comparison and limits."""
    import jax

    from repro.api import VeerConfig
    from repro.engine import InMemoryMaterializationStore
    from repro.engine.plane import get_plane
    from repro.service import VerificationService

    devices = _device_check(cell.chips) if require_tpu else jax.devices()
    _compile_cache(root)
    config = cell.config
    limits = config["limits"]
    compiles: List[float] = []
    counting = {"on": False}

    def on_compile(event: str, duration: float, **_kw) -> None:
        if counting["on"] and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    _log(t0, f"cell {cell.name} seed {seed} on {devices[0].device_kind}")
    tables = datagen.generate(config, seed)
    sources = _program_tables(config, tables)
    _log(t0, "source tables made")
    sessions = Sessions(config, cell.traffic)
    analysts = int(cell.traffic["analysts"])
    store = InMemoryMaterializationStore(byte_budget=int(config["store_byte_budget"]))
    svc = VerificationService(
        config=VeerConfig(plane="jax", exec_mode="delta", evs=("equitas", "spes", "udp")),
        materialization_store=store, keep_certificates=False)
    plane = get_plane("jax")
    instrument = _Instrument() if trace else None
    uninstall = instrument.install(plane) if trace else None
    try:
        first = {a: sessions.session(a, 0) for a in range(analysts)}
        warm = [svc.submit(f"a{a}s0", first[a][0][1], sources=sources)
                for a in range(analysts)]
        for f in warm:
            f.result()
        _log(t0, f"{analysts} base versions answered")
        warmed = _warm_probe_buckets(config, sources)
        setup_s = time.perf_counter() - t0
        _log(t0, f"probe buckets warmed for {warmed} join(s); window opens")

        log_dir = str(root / ".bench_trace")
        loop = ClosedLoop(svc, sessions, analysts, sources, first)
        if trace:
            shutil.rmtree(log_dir, ignore_errors=True)
            jax.profiler.start_trace(log_dir, profiler_options=_profile_options())
            instrument.recording = True
        counting["on"] = True
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            loop.window(seconds)
        counting["on"] = False
        if trace:
            instrument.recording = False
            jax.profiler.stop_trace()
        t_close = time.perf_counter()
        _log(t0, f"window closed, {loop.in_flight} version(s) in flight")
        loop.drain()
        drained_s = time.perf_counter() - t_close
        answers = loop.answers
        stats = devices[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
    finally:
        svc.close(save=False)
        jax.monitoring.unregister_event_duration_listener(on_compile)
        if uninstall:
            uninstall()
    del svc, store

    run = Run(seconds=seconds, setup_s=setup_s, answers=answers,
              failed=sum(a.error is not None for a in answers))
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": None, "attempted": len(answers), "failed": run.failed,
              "metrics": {}, "device": device}
    if trace:
        run.peaks = load_peaks(devices[0].device_kind) if require_tpu else None
        run.probe_calls = list(instrument.probe_calls)
        run.trace = _reduce_trace(log_dir)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["top_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(m["name"])(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    _log(t0, "checking against the reference")
    sample = int(cell.traffic["check_sample"])
    readings = check_answers(answers, tables, seed, sample, limits["sink_gap"])
    _log(t0, "checked")
    verdicts = check.judge(readings, limits)
    result["correct"] = all(v["ok"] for v in verdicts.values())
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]} for k, v in verdicts.items()}
    host = {"peak_rss_bytes": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024,
            "drain_s": drained_s, "refused": loop.refused,
            "compiles_in_window": len(compiles)}
    ctl = None
    if control:
        low = check_answers(control_answers(answers, tables, seed, sample), tables, seed,
                            sample, limits["sink_gap"])
        ctl = {"readings": low,
               "correct": all(v["ok"] for v in check.judge(low, limits).values())}
    return Outcome(result, readings, host, run, ctl)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # TraceAnnotation spans only
    return opts


def load_peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]
