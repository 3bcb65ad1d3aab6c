"""Version-chain verification service (paper §1 workload, ROADMAP north star).

Iterative analytics produces *chains* of dataflow versions: v1 → v2 → … → vn,
each a handful of edits from its predecessor.  ``Veer.verify`` answers one
pair; a ``VersionChainSession`` answers the whole chain while amortizing EV
cost across pairs through the canonical-fingerprint verdict cache
(``repro.core.ev.cache``): a window isomorphic to one decided for *any*
earlier pair — or persisted by an earlier session — resolves without an EV
call.  This is the GEqO/EqDAC observation (cache and share semantic
equivalence sub-results) applied to Veer's windowed decomposition search.

Every decided pair carries a replayable ``repro.api.Certificate`` — cached
cross-session verdicts are auditable evidence, not trust-me (see
``repro.api.certificate``); ``ChainReport.summary()`` shows which pairs are
certificate-backed.

Typical use::

    from repro.api import VeerConfig

    session = VersionChainSession(
        config=VeerConfig(cache_path="~/.veer/verdicts.json")
    )
    session.submit(v1)                  # first version: nothing to verify
    report = session.submit(v2)         # verifies (v1, v2)
    report.certificate.replay()         # audit the verdict, no search
    report = session.submit(v3)         # verifies (v2, v3), reusing verdicts
    print(session.report().summary())
    session.save()                      # persist verdicts for the next session

or, batch-style::

    report = verify_chain([v1, v2, ..., vn])
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

from repro import obs
from repro.api.certificate import Certificate, certificate_from_evidence
from repro.api.config import VeerConfig
from repro.api.registry import EVRegistry
from repro.core import dag as D
from repro.core.dag import DataflowDAG
from repro.core.edits import EditMapping
from repro.core.ev.base import VERDICT_NAMES, BaseEV
from repro.core.ev.cache import VerdictCache
from repro.core.frontier import FrontierError, ReuseFrontier, compute_reuse_frontier
from repro.core.verifier import Veer, VeerStats, make_veer_plus
from repro.engine.executor import ExecStats, ExecutionPlan
from repro.engine.store import MaterializationStore
from repro.engine.table import Table
from repro.service.pair_cache import PairVerdictCache


@dataclass
class PairReport:
    """Verification outcome for one consecutive pair of the chain."""

    index: int                      # pair k verifies (version k-1, version k)
    verdict: Optional[bool]         # True / False / None (Unknown)
    wall_time: float
    stats: VeerStats
    certificate: Optional[Certificate] = None
    # whether the verdict WAS certificate-backed — stays True even when a
    # session with keep_certificates=False drops the payload after returning
    # it to the submit caller
    certified: bool = False
    # verdict + certificate reused wholesale from a PairVerdictCache hit
    # (no search ran for this pair; stats carry only the avoided work)
    reused: bool = False
    # execute-with-reuse mode (sources= passed to submit): accounting for
    # this version's partial execution, the certificate-derived frontier
    # that seeded it, and the sink tables (results are handed to the
    # submit caller only — the session-lifetime report drops them)
    exec_stats: Optional[ExecStats] = None
    frontier: Optional[ReuseFrontier] = None
    results: Optional[Dict[str, Table]] = None

    def __post_init__(self) -> None:
        if self.certificate is not None:
            self.certified = True

    @property
    def equivalent(self) -> bool:
        return self.verdict is True

    @property
    def ev_calls(self) -> int:
        return self.stats.ev_calls

    @property
    def cache_hits(self) -> int:
        return self.stats.cache_hits

    @property
    def ev_calls_saved(self) -> int:
        return self.stats.ev_calls_saved

    def row(self) -> str:
        v = {True: "EQ", False: "NEQ", None: "UNK"}[self.verdict]
        cert = "cert" if self.certified else "----"
        line = (
            f"pair {self.index:>3}: {v:>3}  {cert}  ev_calls={self.ev_calls:<4} "
            f"cache_hits={self.cache_hits:<4} saved={self.ev_calls_saved:<4} "
            f"{self.wall_time * 1e3:8.1f} ms"
            + ("  reused" if self.reused else "")
        )
        if self.exec_stats is not None:
            e = self.exec_stats
            line += (
                f"  exec[{e.ops_executed}/{e.ops_total} ops, "
                f"{e.ops_reused} reused, {e.tables_served} served]"
            )
            if e.ops_delta:
                line += (
                    f"  delta[{e.ops_delta} ops, "
                    f"{e.delta_rows_processed} rows]"
                )
        return line


@dataclass
class ChainReport:
    """Aggregate over all pairs verified so far in a session."""

    pairs: List[PairReport] = field(default_factory=list)
    # execute-with-reuse: accounting for the chain's FIRST version (it has
    # no pair — v1 executes fully and materializes the seed corpus)
    initial_exec: Optional[ExecStats] = None

    @property
    def exec_stats_list(self) -> List[ExecStats]:
        out = [self.initial_exec] if self.initial_exec is not None else []
        out.extend(p.exec_stats for p in self.pairs if p.exec_stats is not None)
        return out

    @property
    def total_ops_executed(self) -> int:
        return sum(e.ops_executed for e in self.exec_stats_list)

    @property
    def total_ops_reused(self) -> int:
        return sum(e.ops_reused for e in self.exec_stats_list)

    @property
    def total_tables_served(self) -> int:
        return sum(e.tables_served for e in self.exec_stats_list)

    @property
    def total_ops(self) -> int:
        return sum(e.ops_total for e in self.exec_stats_list)

    @property
    def total_ops_delta(self) -> int:
        """Operators whose outputs came from delta rules, chain-wide."""
        return sum(e.ops_delta for e in self.exec_stats_list)

    @property
    def total_delta_rows_processed(self) -> int:
        """Delta rows (inserts + deletes) the delta rules touched — the
        O(|Δ|) work that replaced full re-execution."""
        return sum(e.delta_rows_processed for e in self.exec_stats_list)

    @property
    def total_recompute_time_saved(self) -> float:
        """Recorded original compute cost of every table served instead of
        recomputed (store-recorded seconds)."""
        return sum(e.recompute_time_saved for e in self.exec_stats_list)

    @property
    def executed_fraction(self) -> float:
        """Share of all chain operators that actually ran ``execute_op`` —
        the headline the exec benchmark bounds (≤ 0.30 on the 12-version
        workload with a warm verdict cache)."""
        return self.total_ops_executed / max(1, self.total_ops)

    @property
    def total_ev_calls(self) -> int:
        return sum(p.ev_calls for p in self.pairs)

    @property
    def total_cache_hits(self) -> int:
        return sum(p.cache_hits for p in self.pairs)

    @property
    def total_ev_calls_saved(self) -> int:
        return sum(p.ev_calls_saved for p in self.pairs)

    @property
    def total_wall_time(self) -> float:
        return sum(p.wall_time for p in self.pairs)

    @property
    def verdicts(self) -> List[Optional[bool]]:
        return [p.verdict for p in self.pairs]

    @property
    def certified_pairs(self) -> int:
        return sum(1 for p in self.pairs if p.certified)

    @property
    def reused_pairs(self) -> int:
        """Pairs answered wholesale from the shared pair-verdict cache."""
        return sum(1 for p in self.pairs if p.reused)

    @property
    def certified_fraction(self) -> float:
        """Share of *decided* (True/False) pairs backed by a certificate."""
        decided = [p for p in self.pairs if p.verdict is not None]
        if not decided:
            return 0.0
        return sum(1 for p in decided if p.certified) / len(decided)

    def summary(self) -> str:
        lines = [p.row() for p in self.pairs]
        lines.append(
            f"chain: {len(self.pairs)} pairs, "
            f"{self.certified_pairs} certificate-backed, "
            f"{self.total_ev_calls} EV calls, "
            f"{self.total_cache_hits} cache hits, "
            f"{self.total_ev_calls_saved} calls saved, "
            f"{self.total_wall_time * 1e3:.1f} ms"
        )
        if self.exec_stats_list:
            lines.append(
                f"exec:  {self.total_ops_executed}/{self.total_ops} ops "
                f"executed ({100.0 * self.executed_fraction:.0f}%), "
                f"{self.total_ops_reused} reused, "
                f"{self.total_tables_served} tables served"
            )
        if self.total_ops_delta:
            lines.append(
                f"delta: {self.total_ops_delta} ops via delta rules, "
                f"{self.total_delta_rows_processed} delta rows, "
                f"{self.total_recompute_time_saved * 1e3:.1f} ms "
                f"recompute saved"
            )
        return "\n".join(lines)


class VersionChainSession:
    """Stateful chain-verification service around a cache-backed ``Veer``.

    Each ``submit`` verifies the new version against the previous one; all
    pairs share one ``VerdictCache`` (optionally persisted at ``cache_path``
    and/or shared with a ``ReuseManager``'s store directory), so pair *k*
    pays EV cost only for windows no earlier pair or session has decided.
    """

    def __init__(
        self,
        evs: Optional[Sequence[BaseEV]] = None,
        *,
        config: Optional[VeerConfig] = None,
        registry: Optional[EVRegistry] = None,
        cache: Optional[VerdictCache] = None,
        cache_path: Optional[str] = None,
        semantics: Optional[str] = None,
        veer: Optional[Veer] = None,
        keep_certificates: bool = True,
        pair_cache: Optional["PairVerdictCache"] = None,
        materialization_store: Optional[MaterializationStore] = None,
        **veer_kw,
    ):
        """The preferred construction path is ``config=VeerConfig(...)``
        (EVs by name, resolved through ``registry``); ``evs``/``veer`` and
        ``**veer_kw`` remain as deprecated shims for pre-``repro.api``
        callers.  Cache precedence: explicit ``cache`` > ``cache_path`` >
        ``config.cache_path`` > in-memory.

        ``keep_certificates=False`` drops certificate payloads from the
        session-lifetime report after each ``submit`` returns (the caller
        still receives the full certificate; ``PairReport.certified`` stays
        truthful) — for very long monitoring sessions whose report must not
        accumulate per-pair window payloads.

        ``pair_cache`` (a shared ``repro.service.pair_cache
        .PairVerdictCache``) short-circuits whole pairs already decided by
        any session sharing the cache: a content-digest hit reuses the
        original verdict *and certificate* without running the search —
        this is how a ``VerificationService`` answers N clients evolving
        the same pipeline for one client's worth of work.

        ``materialization_store`` enables **execute-with-reuse**: pass
        ``sources=`` to ``submit`` and the session executes each version
        through an ``ExecutionPlan``, materializing operator outputs into
        the store and seeding every successor from the certificate-derived
        reuse frontier (``repro.core.frontier``) — v1 runs fully, each
        later version recomputes only its changed cone.  Seeding is taken
        only from exact-tier frontier entries whose content digests match,
        so the returned sink tables are bit-identical to a full
        re-execution; frontier reuse is only ever taken when the pair's
        certificate replays green against the pair."""
        if config is not None and (evs is not None or veer is not None or veer_kw):
            raise ValueError("pass either config or evs/veer/veer_kw, not both")
        if veer is not None and (evs is not None or veer_kw):
            raise ValueError("pass either veer or evs/veer_kw, not both")
        if cache is not None and cache_path is not None:
            raise ValueError("pass either cache or cache_path, not both")
        if config is None and evs is None and veer is None and not veer_kw:
            config = VeerConfig()
        if cache is None:
            path = cache_path if cache_path is not None else (
                config.cache_path if config is not None else None
            )
            # honor the config's LRU bound so long-lived sessions do not
            # accumulate verdict/validity entries without limit
            cache = VerdictCache(
                path,
                max_entries=(
                    config.cache_max_entries if config is not None else None
                ),
            )
        self.cache = cache
        self.config = config
        if config is not None:
            veer = config.build(registry, cache=cache)
        elif veer is None:
            # deprecated path: explicit EV instances and/or raw Veer kwargs
            # keep their pre-api semantics (forwarded to make_veer_plus)
            from repro.api.registry import default_registry

            evs = list(evs) if evs is not None else default_registry().build()
            veer = make_veer_plus(evs, **veer_kw)
        self.veer = veer.attach_cache(cache)
        if semantics is None:
            semantics = config.semantics if config is not None else D.BAG
        self.semantics = semantics
        # data plane for execute-with-reuse submits; plane-invariant bytes
        # keep store keys / frontier digests / certificates unchanged
        self.plane = config.plane if config is not None else "numpy"
        # how successor versions execute: full / reuse / delta (mode-invariant
        # sink bytes; "delta" falls back to the seeded reuse run whenever the
        # edit is not amenable or a required table left the store)
        self.exec_mode = config.exec_mode if config is not None else "reuse"
        self.keep_certificates = keep_certificates
        self.pair_cache = pair_cache
        self.store = materialization_store
        self._registry = registry
        # only the previous version is needed for the next pair; a long-lived
        # session must not accumulate every DAG it ever saw
        self._prev: Optional[DataflowDAG] = None
        self._prev_plan: Optional[ExecutionPlan] = None
        self.version_count = 0
        self._report = ChainReport()

    # -- service API ---------------------------------------------------------
    def submit(
        self,
        version: DataflowDAG,
        mapping: Optional[EditMapping] = None,
        *,
        sources: Optional[Dict[str, Table]] = None,
    ) -> Optional[PairReport]:
        """Append a version; verify it against the previous one.

        ``mapping`` is the tracked edit mapping from the previous version to
        this one (defaults to the id-stable identity mapping, the natural
        choice when the version-control layer assigns stable operator ids).
        Returns ``None`` for the first version (nothing to verify yet).

        ``sources`` (execute-with-reuse mode; needs a session
        ``materialization_store``) additionally *executes* the version:
        the first version runs fully, successors recompute only the cone
        the edit touched, seeded from exact-tier frontier entries of the
        pair's replay-green certificate.  The returned report then carries
        ``exec_stats``, the ``frontier``, and the sink ``results`` —
        including for the **first** version, which gets a report (verdict
        ``None``, nothing to verify) instead of the verify-only ``None``.
        """
        with obs.span("veer.chain.plan", index=self.version_count):
            version.validate()
            if sources is not None and self.store is None:
                # checked before any session state moves: a rejected submit
                # must leave the chain exactly where it was
                raise ValueError(
                    "execute-with-reuse needs a session materialization_store"
                )
            prev, self._prev = self._prev, version
            self.version_count += 1
            plan: Optional[ExecutionPlan] = None
            if sources is not None:
                plan = ExecutionPlan(version, sources, plane=self.plane)
            prev_plan, self._prev_plan = self._prev_plan, plan

        if prev is None:
            if plan is None:
                return None
            res = plan.run(store=self.store, materialize=True)
            self._report.initial_exec = res.stats
            return PairReport(
                index=0,
                verdict=None,
                wall_time=res.stats.wall_time,
                stats=VeerStats(),
                exec_stats=res.stats,
                results=res.results,
            )

        t0 = time.perf_counter()
        with obs.span("veer.search.decide") as sp:
            verdict, stats, certificate, reused = self._decide(
                prev, version, mapping
            )
            sp.set_metadata(verdict=VERDICT_NAMES[verdict], reused=int(reused),
                            decompositions=stats.decompositions_explored,
                            ev_calls=stats.ev_calls, sat_calls=stats.sat_calls)
        exec_stats = frontier = results = None
        if plan is not None:
            if self.exec_mode == "full":
                res = plan.run(store=self.store, materialize=True)
            else:
                with obs.span("veer.exec.frontier"):
                    frontier, seed_keys = self._frontier_seeds(
                        prev, version, certificate, verdict, prev_plan, plan
                    )
                res = None
                if self.exec_mode == "delta" and frontier is not None:
                    res = self._try_delta(frontier, prev, prev_plan, plan)
                if res is None:
                    res = plan.run(
                        store=self.store, seed_keys=seed_keys,
                        materialize=True,
                    )
            exec_stats, results = res.stats, res.results
        report = PairReport(
            index=self.version_count - 1,
            verdict=verdict,
            wall_time=time.perf_counter() - t0,
            stats=stats,
            certificate=certificate,
            reused=reused,
            exec_stats=exec_stats,
            frontier=frontier,
            results=results,
        )
        # the session-lifetime report never accumulates sink tables; the
        # certificate/frontier payloads follow keep_certificates
        stored = dataclasses.replace(report, results=None)
        if not self.keep_certificates:
            stored = dataclasses.replace(stored, certificate=None, frontier=None)
        self._report.pairs.append(stored)
        return report

    def _frontier_seeds(
        self,
        prev: DataflowDAG,
        version: DataflowDAG,
        certificate: Optional[Certificate],
        verdict: Optional[bool],
        prev_plan: Optional[ExecutionPlan],
        plan: ExecutionPlan,
    ):
        """Certificate-gated seeding for this version's partial execution.

        Only a True verdict whose certificate **replays green bound to the
        pair** yields a frontier (``compute_reuse_frontier`` enforces it);
        only *exact-tier* entries are seeded, and each one additionally
        requires digest equality between the Q operator's cone (current
        sources folded in) and the P operator's materialized table — so a
        source rebinding or any mismatch falls back to recomputation and
        the executed results stay bit-identical to a full run.
        """
        if verdict is not True or certificate is None or prev_plan is None:
            return None, {}
        try:
            frontier = compute_reuse_frontier(
                certificate, prev, version, registry=self._registry
            )
        except FrontierError:
            return None, {}
        prev_digests = prev_plan.digests
        cur_digests = plan.digests
        seed_keys = {}
        for q_op, p_op in frontier.exact.items():
            key = prev_digests.get(p_op)
            if key is not None and cur_digests.get(q_op) == key:
                seed_keys[q_op] = key
        return frontier, seed_keys

    def _try_delta(
        self,
        frontier: ReuseFrontier,
        prev: DataflowDAG,
        prev_plan: Optional[ExecutionPlan],
        plan: ExecutionPlan,
    ):
        """Delta tier: O(|Δrows|) propagation through the changed cone.

        Engages only on a frontier from ``_frontier_seeds`` — i.e. a True
        verdict whose certificate replayed green for the pair — and only
        when the edit is statically amenable (``compute_delta_plan``).
        Returns ``None`` on any fallback condition (not amenable, a table
        evicted mid-chain, a byte-identity precondition violated at run
        time), and the caller takes the seeded reuse run instead — the
        sink bytes are identical either way, only the cost differs.
        """
        if prev_plan is None:
            return None
        from repro.core.frontier import compute_delta_plan
        from repro.engine.delta import DeltaUnsupported, execute_delta

        dplan = compute_delta_plan(frontier, prev, plan.dag)
        if dplan is None:
            return None
        try:
            return execute_delta(
                dplan, prev, plan, prev_plan.digests, self.store
            )
        except DeltaUnsupported:
            return None

    def _decide(
        self,
        prev: DataflowDAG,
        version: DataflowDAG,
        mapping: Optional[EditMapping],
    ):
        """Verify one pair, going through the shared pair-verdict cache
        when one is attached (single-flight: concurrent sessions deciding
        the same content-identical pair run the search exactly once)."""
        def compute():
            verdict, stats, evidence = self.veer.verify_with_evidence(
                prev, version, mapping, semantics=self.semantics
            )
            return verdict, stats, certificate_from_evidence(evidence)

        if self.pair_cache is None:
            verdict, stats, certificate = compute()
            return verdict, stats, certificate, False
        key = self.pair_cache.make_key(prev, version, self.semantics, mapping)
        return self.pair_cache.compute_or_reuse(
            key, compute, pair=(prev, version)
        )

    def report(self) -> ChainReport:
        return self._report

    def save(self) -> None:
        """Persist the verdict cache (no-op for purely in-memory caches)."""
        self.cache.save()

    def close(self) -> None:
        """Persist the cache and release the verifier's window-dispatch
        pool (relevant for ``VeerConfig(max_workers > 1)``); the session
        remains usable — the pool is recreated on the next parallel run."""
        self.save()
        self.veer.close()

    def __enter__(self) -> "VersionChainSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def verify_chain(
    versions: Sequence[DataflowDAG],
    mappings: Optional[Sequence[Optional[EditMapping]]] = None,
    **session_kw,
) -> ChainReport:
    """Batch entry point: verify every consecutive pair of ``versions``.

    ``mappings[k]`` (optional) maps version k to version k+1.
    """
    if mappings is not None and len(mappings) != len(versions) - 1:
        raise ValueError("need exactly one mapping per consecutive pair")
    session = VersionChainSession(**session_kw)
    for k, v in enumerate(versions):
        session.submit(v, mappings[k - 1] if mappings and k > 0 else None)
    session.save()
    return session.report()
