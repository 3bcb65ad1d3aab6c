"""Smoke run of Veer's device path on one TPU chip.

Drives the main path once through the entry points a user calls, at the
largest size the repository uses (1M source rows), and checks every
result against the numpy reference plane.  One process; no phase's error
is caught, so any failure exits non-zero before the result line.

  1. device   the default device must be a TPU (never falls back to CPU)
  2. plane    build the jax plane: exactness-probe outcome and the compile
              seconds of each kernel family at the 1M-row bucket
  3. session  6-version heavy chain through VersionChainSession on the jax
              plane (exec_mode="reuse"): sinks byte-identical to full
              numpy-plane execution, every certificate replays green
  4. delta    the narrow/widen filter chain (repro.workload.flows) with
              exec_mode="delta": sinks identical, every pair certified
  5. hot      the hot-operator flow (repro.workload.flows; warm-up call,
              then the timed call) plus a join whose combined key codes are
              sparse, so the jitted join probe runs on the chip
  6. fleet    a 2-worker VerificationFleet (host processes, default plane)
              over a short chain, started after the plane has taken the chip

Each phase prints one line of counts and wall seconds: information, not
metrics.  The last line is one JSON object naming the device.

Usage (from the repository root):

    python chip_smoke.py               # 1M rows; needs a TPU
    python chip_smoke.py --rows 50000  # a smaller run of the same phases

``run(rows)`` holds phases 2-6 without the device check; the tests call it
on the CPU at small sizes.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import pathlib
import sys
import threading
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.api import VeerConfig  # noqa: E402
from repro.api.registry import default_registry  # noqa: E402
from repro.core import dag as D  # noqa: E402
from repro.engine import (  # noqa: E402
    InMemoryMaterializationStore,
    Table,
    execute,
    tables_identical,
)
from repro.engine.executor import ExecutionPlan  # noqa: E402
from repro.engine.plane import get_plane  # noqa: E402
from repro.engine.plane.jax_plane import use_compile_cache  # noqa: E402
from repro.service import VerificationFleet, VersionChainSession  # noqa: E402
from repro.service.synthetic import make_chain  # noqa: E402
from repro.workload.flows import (  # noqa: E402
    hot_operator_flow,
    hot_operator_sources,
    narrow_widen_chain,
    narrow_widen_sources,
)

FULL_ROWS = 1_000_000
KERNEL_BUCKET = 1 << 20  # the power-of-two bucket a 1M-row table pads to
FLEET_TIMEOUT_S = 300.0


def _fail(msg: str):
    raise SystemExit(f"FAIL: {msg}")


def _abort(msg: str) -> None:
    """Deadline expiry: stop every child process and exit at once (a hung
    worker would also hang the fleet's drain on a normal exit)."""
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    for child in multiprocessing.active_children():
        child.kill()
    os._exit(1)


def _line(phase: str, t0: float, **counts) -> None:
    fields = " ".join(f"{k}={v}" for k, v in counts.items())
    print(f"[{phase}] {fields} wall_s={time.perf_counter() - t0:.3f}", flush=True)


def _check_sinks(what: str, got, want) -> None:
    if set(got) != set(want):
        _fail(f"{what}: sinks {sorted(got)} != reference {sorted(want)}")
    for s in want:
        if not tables_identical(got[s], want[s]):
            _fail(f"{what}: sink {s} differs from the numpy reference")


def _check_certified(what: str, reports, chain) -> int:
    registry = default_registry()
    for k in range(1, len(chain)):
        r = reports[k]
        if r.verdict is not True or not r.certified or r.certificate is None:
            _fail(f"{what}: pair {k} verdict {r.verdict}, certified={r.certified}")
        rep = r.certificate.replay(registry, chain[k - 1], chain[k])
        if not rep.ok:
            _fail(f"{what}: pair {k} certificate replay: {rep.summary()}")
    return len(chain) - 1


def _exec_counts(reports) -> dict:
    stats = [r.exec_stats for r in reports if r.exec_stats is not None]
    return {
        "ops_lowered": sum(s.ops_lowered for s in stats),
        "ops_on_device": sum(s.ops_on_device for s in stats),
        "ops_delta": sum(s.ops_delta for s in stats),
    }


def phase_device() -> dict:
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        _fail(f"no TPU: the default device is {dev.platform}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}


def phase_plane() -> None:
    import jax

    t0 = time.perf_counter()
    plane = get_plane("jax")
    exact = plane.values_exact()
    compile_s = {}
    with jax.enable_x64(True):
        for name, fn, args in plane.kernel_specs(KERNEL_BUCKET):
            t = time.perf_counter()
            jax.jit(fn).lower(*args).compile()
            compile_s[name] = round(time.perf_counter() - t, 3)
    _line("plane", t0, probe_exact=exact, probe_mismatch=repr(plane.probe_mismatch),
          compile_s=json.dumps(compile_s, separators=(",", ":")))


def _uniform_sources(version, rows: int, seed: int):
    rng = np.random.default_rng(seed)
    out = {}
    for sid in sorted(version.sources):
        schema = list(version.ops[sid].get("schema"))
        out[sid] = Table(
            {c: rng.integers(0, 7, rows).astype(np.float64) for c in schema},
            schema,
        )
    return out


def phase_session(rows: int) -> None:
    t0 = time.perf_counter()
    chain = make_chain(6, heavy=True)
    sources = _uniform_sources(chain[0], rows, seed=0)
    session = VersionChainSession(
        config=VeerConfig(plane="jax", exec_mode="reuse"),
        materialization_store=InMemoryMaterializationStore(),
    )
    reports = [session.submit(v, sources=sources) for v in chain]
    t_jax = time.perf_counter() - t0
    t = time.perf_counter()
    for k, v in enumerate(chain):
        _check_sinks(f"session v{k}", reports[k].results,
                     execute(v, sources, plane="numpy"))
    t_ref = time.perf_counter() - t
    replayed = _check_certified("session", reports, chain)
    _line("session", t0, rows=rows, versions=len(chain), replayed=replayed,
          jax_session_s=f"{t_jax:.3f}", numpy_reference_s=f"{t_ref:.3f}",
          **_exec_counts(reports))


def phase_delta(rows: int) -> None:
    t0 = time.perf_counter()
    chain = narrow_widen_chain()
    sources = narrow_widen_sources(rows)
    session = VersionChainSession(
        config=VeerConfig(evs=("equitas", "spes", "udp"), plane="jax",
                          exec_mode="delta"),
        materialization_store=InMemoryMaterializationStore(),
    )
    reports = [session.submit(v, sources=sources) for v in chain]
    t_jax = time.perf_counter() - t0
    t = time.perf_counter()
    for k, v in enumerate(chain):
        _check_sinks(f"delta v{k}", reports[k].results,
                     execute(v, sources, plane="numpy"))
    t_ref = time.perf_counter() - t
    certified = _check_certified("delta", reports, chain)
    counts = _exec_counts(reports)
    if counts["ops_delta"] == 0:
        _fail("delta: no operator went through a delta rule")
    _line("delta", t0, rows=rows, versions=len(chain), certified=certified,
          jax_session_s=f"{t_jax:.3f}", numpy_reference_s=f"{t_ref:.3f}",
          **counts)


def _sparse_join(s1: Table, seed: int = 2):
    """s1 ⋈ r on (k, g), where r holds the keys of a quarter of s1's rows:
    the combined codes (~rows × 1024) run far past the dense lookup range,
    so the probe is the jitted one, and every r row finds a match."""
    ops = [
        D.Operator.make("s1", D.SOURCE, schema=tuple(s1.order)),
        D.Operator.make("r", D.SOURCE, schema=("k", "g", "y")),
        D.Operator.make("j", D.JOIN, on=(("k", "k"), ("g", "g")), how="inner"),
        D.Operator.make("sink", D.SINK, semantics=D.BAG),
    ]
    links = [D.Link("s1", "j", 0), D.Link("r", "j", 1), D.Link("j", "sink")]
    rng = np.random.default_rng(seed)
    idx = rng.choice(len(s1), size=max(len(s1) // 4, 1), replace=False)
    r = Table({"k": s1.cols["k"][idx], "g": s1.cols["g"][idx],
               "y": rng.integers(0, 7, len(idx)).astype(np.float64)},
              ["k", "g", "y"])
    return D.DataflowDAG(ops, links), {"s1": s1, "r": r}


def phase_hot(rows: int) -> None:
    t0 = time.perf_counter()
    dag = hot_operator_flow()
    execute(dag, hot_operator_sources(rows, seed=1), plane="jax")  # warm-up
    sources = hot_operator_sources(rows)
    t = time.perf_counter()
    res = ExecutionPlan(dag, sources, plane="jax").run()
    t_jax = time.perf_counter() - t
    t = time.perf_counter()
    _check_sinks("hot chain", res.results, execute(dag, sources, plane="numpy"))
    t_ref = time.perf_counter() - t

    jdag, jsources = _sparse_join(sources["s1"])
    t = time.perf_counter()
    jres = ExecutionPlan(jdag, jsources, plane="jax").run()
    t_join = time.perf_counter() - t
    _check_sinks("sparse join", jres.results,
                 execute(jdag, jsources, plane="numpy"))
    if jres.stats.ops_on_device != 1:
        _fail("sparse join: the jitted join probe did not run on the device")
    _line("hot", t0, rows=rows, ops_lowered=res.stats.ops_lowered,
          ops_on_device=res.stats.ops_on_device, jax_chain_s=f"{t_jax:.3f}",
          numpy_chain_s=f"{t_ref:.3f}", join_rows_out=len(jres.results["sink"]),
          join_ops_on_device=jres.stats.ops_on_device,
          jax_join_s=f"{t_join:.3f}")


def phase_fleet() -> None:
    t0 = time.perf_counter()
    chain = make_chain(4)
    deadline = threading.Timer(
        FLEET_TIMEOUT_S, _abort, (f"fleet: no end within {FLEET_TIMEOUT_S}s",)
    )
    deadline.daemon = True
    deadline.start()
    try:
        with VerificationFleet(2) as fleet:
            futures = {c: [fleet.submit(c, v) for v in chain] for c in ("a", "b")}
            reports = {c: [f.result() for f in fs] for c, fs in futures.items()}
            report = fleet.drain()
    finally:
        deadline.cancel()
    if report.errors:
        _fail(f"fleet: {report.errors}")
    replayed = sum(_check_certified(f"fleet client {c}", rs, chain)
                   for c, rs in reports.items())
    _line("fleet", t0, workers=2, clients=len(reports), replayed=replayed)


def run(rows: int) -> None:
    """Phases 2-6 on the default device."""
    phase_plane()
    phase_session(rows)
    phase_delta(rows)
    phase_hot(rows)
    phase_fleet()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=FULL_ROWS,
                    help=f"source rows per phase (default {FULL_ROWS})")
    args = ap.parse_args()
    use_compile_cache(ROOT)
    t0 = time.perf_counter()
    device = phase_device()
    run(args.rows)
    print(f"[total] wall_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
