"""A window's query pair read through the version DAGs
(``repro.core.window.WindowDAG``) equals the sub-DAGs built for it.

For every window the bitmask search admits, over pairs of the flows W1-W8
and sessions of the tiny Q40 and Q50 cells: the same well-formedness, the
same operators and links in the same order, the same fingerprint, each
EV's restriction list and, where the EV is valid, its verdict (on at most
``CHECKED`` windows a flow, spread over the search's order: W4 admits
about 4,000); the search builds sub-DAGs only for its certificates; and
the certificates are byte-identical to the reference backend's.
"""

import json

import pytest

from repro.api.certificate import certificate_from_evidence
from repro.core.dag import DataflowDAG
from repro.core.ev import EquitasEV, JaxprEV, SpesEV, UDPEV
from repro.core.ev.cache import VerdictCache
from repro.core.verifier import make_veer_plus
from repro.workload.flows import WORKLOADS, apply_equivalent_edits, apply_inequivalent_edits

SEARCH_EVS = [EquitasEV(), SpesEV(), UDPEV()]  # the benchmark's roster
EVS = SEARCH_EVS + [JaxprEV()]
CHECKED = 400
JAXPR_CHECKS = 3  # jaxpr verdicts compared per flow: each traces two programs


def _w_pairs(name):
    P = WORKLOADS[name]()
    return [(P, apply_equivalent_edits(P, n=3, seed=5), None),
            (P, apply_inequivalent_edits(P, 1, seed=1), None)]


def _tiny_pairs(module):
    from bench import harness

    cell = module.tiny_cell()
    versions = harness.Sessions(cell.config, cell.traffic).session(0, 0)[:4]
    return [(p, q, m) for (_, p, _), (_, q, m) in zip(versions, versions[1:])]


def _pairs(flow):
    if flow == "q40":
        from bench.tests import tiny
        return _tiny_pairs(tiny)
    if flow == "q50":
        from bench.tests import tiny_q50
        return _tiny_pairs(tiny_q50)
    return _w_pairs(flow)


FLOWS = sorted(WORKLOADS) + ["q40", "q50"]


def _search(P, Q, mapping, backend, admitted=None):
    veer = make_veer_plus(SEARCH_EVS, verdict_cache=VerdictCache(),
                          search_backend=backend, max_decompositions=2000)
    if admitted is not None:
        accept = veer._accept_window_id

        def recording(ctx, wid):
            if ctx.table.connected(wid):
                admitted[(id(ctx.table), wid)] = (ctx.table, wid)
            return accept(ctx, wid)

        veer._accept_window_id = recording
    verdict, stats, evidence = veer.verify_with_evidence(P, Q, mapping)
    cert = certificate_from_evidence(evidence)
    return verdict, stats, cert.to_json() if cert is not None else None


def _structure(dag):
    return (list(dag.ops.items()), list(dag.in_links.items()),
            list(dag.out_links.items()), list(dag.links))


@pytest.mark.parametrize("flow", FLOWS)
def test_every_admitted_window_reads_as_its_sub_dags(flow):
    admitted = {}
    for P, Q, m in _pairs(flow):
        _search(P, Q, m, "bitmask", admitted)
    formed = []
    for table, wid in admitted.values():
        view = table.query_pair(wid)
        built = table.pair.to_query_pair(table.frozen(wid))
        assert (table.boundary(wid) is None) == (view is None) == (built is None)
        if built is None:
            continue
        formed.append((view, built))
        assert isinstance(built.P, DataflowDAG) and not isinstance(view.P, DataflowDAG)
        for v, b in ((view.P, built.P), (view.Q, built.Q)):
            assert _structure(v) == _structure(b)
        assert (view.sink_pairs, view.semantics, view.at_version_sink) == (
            built.sink_pairs, built.semantics, built.at_version_sink)
        assert view.fingerprint() == built.fingerprint() == table.fingerprint(wid)
        for ev in EVS:
            assert ev.failed_restrictions(view) == ev.failed_restrictions(built), ev.name
    assert formed
    jaxpr_checks = 0
    for view, built in formed[::-(-len(formed) // CHECKED)]:
        for ev in EVS:
            if not ev.validate(built):
                continue
            if isinstance(ev, JaxprEV):
                if jaxpr_checks == JAXPR_CHECKS:
                    continue
                jaxpr_checks += 1
            assert ev.check(view) == ev.check(built), ev.name


@pytest.mark.parametrize("flow", FLOWS)
def test_certificates_equal_the_reference_backends_and_only_they_build_sub_dags(flow):
    decided = 0
    for P, Q, m in _pairs(flow):
        verdict, stats, cert = _search(P, Q, m, "bitmask")
        assert (verdict, cert) == _search(P, Q, m, "reference")[::2]
        decided += cert is not None
        windows = json.loads(cert)["windows"] if cert is not None else []
        assert stats.subdags == sum(w["kind"] == "ev" for w in windows)
        assert stats.subdags <= stats.windows_admitted
    assert decided
