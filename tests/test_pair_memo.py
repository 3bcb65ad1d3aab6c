"""The per-pair memo of the EVs' work and the solver it fronts
(``repro.core.ev.memo``, ``repro.core.ev.solver``) change no answer:

* satisfiability, with equalities substituted out and disequalities split
  into both strict sides, equals plain Fourier-Motzkin (an equality as two
  opposite rows, every side of every disequality tried) on seeded random
  systems, memoized or not, and never calls a system with a rational
  witness unsatisfiable;
* window fingerprints equal the serialization they replaced;
* on fixed TPC-DS Q40 and Q50 sessions every verdict and certificate
  equals the parent commit's, and Q50's semantic pairs decide at most
  half the satisfiability problems the parent did.
"""

import hashlib
import itertools
import json
import pathlib
import random
from fractions import Fraction

import pytest

from repro.core.dag import SOURCE
from repro.core.ev import memo, solver
from repro.core.predicates import LinCmp, LinExpr

ROOT = pathlib.Path(__file__).resolve().parent.parent
VARS = ("x", "y", "z")


def _fm_oracle(atoms):
    rows, diseqs = [], []
    for a in atoms:
        d, c = dict(a.expr.coeffs), a.expr.const
        if a.op in ("<=", "<"):
            rows.append((d, c, a.op == "<"))
        elif a.op == "==":
            rows += [(d, c, False), ({k: -v for k, v in d.items()}, -c, False)]
        else:
            diseqs.append((d, c))
    for signs in itertools.product((1, -1), repeat=len(diseqs)):
        sides = [({k: s * v for k, v in d.items()}, s * c, True)
                 for s, (d, c) in zip(signs, diseqs)]
        if solver._fm_satisfiable(rows + sides):
            return True
    return False


def _random_system(rng):
    atoms = []
    for _ in range(rng.randint(1, 7)):
        cols = rng.sample(VARS, rng.randint(0 if rng.random() < 0.05 else 1, 3))
        coeffs = {c: rng.choice([-3, -2, -1, 1, 2, 3]) for c in cols}
        op = rng.choices(["<=", "<", "==", "!="], [0.35, 0.25, 0.25, 0.15])[0]
        atoms.append(LinCmp(LinExpr.make(coeffs, rng.randint(-6, 6)), op))
    return atoms


_GRID = [Fraction(n, 4) for n in range(-24, 25)]


def _has_grid_witness(atoms):
    """A rational point (in [-6, 6], in quarters) satisfying all atoms,
    over systems in x and y."""
    holds = {"<=": lambda v: v <= 0, "<": lambda v: v < 0,
             "==": lambda v: v == 0, "!=": lambda v: v != 0}
    for x in _GRID:
        for y in _GRID:
            point = {"x": x, "y": y}
            if all(holds[a.op](sum(v * point[c] for c, v in a.expr.coeffs) + a.expr.const)
                   for a in atoms):
                return True
    return False


def test_satisfiable_equals_the_fourier_motzkin_oracle():
    rng = random.Random(20261018)
    systems = [_random_system(rng) for _ in range(600)]
    pair = memo.PairMemo()
    with memo.scope(pair):
        for atoms in systems:
            want = _fm_oracle(atoms)
            assert solver.satisfiable(atoms) is want, atoms
            # a repeat, reordered and with a duplicate, is looked up
            again = list(reversed(atoms)) + atoms[:1]
            assert solver.satisfiable(again) is want, atoms
    assert len(pair.sat) == len({frozenset(a) for a in systems})
    for atoms in systems[:200]:
        assert solver.satisfiable(atoms) is _fm_oracle(atoms)  # no memo active
    kinds = [sum(a.op == "!=" for a in s) for s in systems]
    assert max(kinds) >= 2 and sum(any(a.op == "==" for a in s) for s in systems) > 100


def test_a_system_with_a_rational_witness_is_satisfiable():
    rng = random.Random(7)
    checked = 0
    for _ in range(120):
        atoms = [a for a in _random_system(rng) if a.expr.columns <= {"x", "y"}]
        if atoms and _has_grid_witness(atoms):
            assert solver.satisfiable(atoms) and _fm_oracle(atoms), atoms
            checked += 1
    assert checked > 20


def test_two_disequalities_are_split_together():
    """y >= 1, x != 0, y != 0 holds at x = y = 1.  Choosing a side for
    y != 0 without the other atoms (y < 0) and then giving up called it
    unsatisfiable."""
    y_ge_1 = LinCmp(LinExpr.make({"y": -1}, 1), "<=")
    atoms = [y_ge_1, LinCmp(LinExpr.make({"x": 1}), "!="), LinCmp(LinExpr.make({"y": 1}), "!=")]
    assert solver.satisfiable(atoms) is True
    assert solver.implies([y_ge_1, atoms[1]], LinCmp(LinExpr.make({"y": 1}), "==")) is False


# -- fingerprints -----------------------------------------------------------
def _canon_cone_oracle(dag, root, source_tokens, node_ix, out):
    stack = [("visit", root)]
    while stack:
        action, op_id = stack.pop()
        if action == "end":
            node_ix[op_id] = len(node_ix)
            out.append(("end",))
            continue
        op = dag.ops[op_id]
        if op.op_type == SOURCE:
            tok = source_tokens.setdefault(op_id, len(source_tokens))
            out.append(("src", tok, op.signature()))
            continue
        if op_id in node_ix:
            out.append(("ref", node_ix[op_id]))
            continue
        out.append(("begin", op.signature()))
        stack.append(("end", op_id))
        for link in reversed(dag.in_links.get(op_id, ())):
            stack.append(("visit", link.src))


def _fingerprint_oracle(qp):
    """``QueryPair.fingerprint`` as it was: the stream built as tuples and
    written out with ``repr``."""
    pairs = []
    for ps, qs in qp.sink_pairs:
        tokens, local = {}, []
        _canon_cone_oracle(qp.P, ps, tokens, {}, local)
        local.append(("side",))
        _canon_cone_oracle(qp.Q, qs, tokens, {}, local)
        pairs.append((repr(local), ps, qs))
    pairs.sort(key=lambda x: x[0])
    tokens, ix_p, ix_q, stream = {}, {}, {}, []
    for _, ps, qs in pairs:
        stream.append(("sink",))
        _canon_cone_oracle(qp.P, ps, tokens, ix_p, stream)
        stream.append(("side",))
        _canon_cone_oracle(qp.Q, qs, tokens, ix_q, stream)
    blob = repr((qp.semantics, qp.at_version_sink, stream))
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def test_lazy_sort_keys_order_as_the_whole_serializations_do():
    """Sink pairs sort by their serializations drawn item by item, as
    they sorted by the whole ``repr`` of each list: sequences sharing
    prefixes, one a prefix of another, and equal ones (kept in order)."""
    from repro.core.ev.base import _Items, _repr_list

    rng = random.Random(3)
    words = [repr(t) for t in (("end",), ("side",), ("ref", 1), ("ref", 12), ("src", 0, "a"),
                               ("begin", ("Filter", (("pred", "x < 1"),))), ("begin", "(")) ]
    for _ in range(300):
        stem = [rng.choice(words) for _ in range(rng.randint(0, 4))]
        seqs = [stem + [rng.choice(words) for _ in range(rng.randint(0, 3))] for _ in range(6)]
        want = sorted(range(len(seqs)), key=lambda i: _repr_list(seqs[i]))
        got = sorted(range(len(seqs)), key=lambda i: _Items(iter(seqs[i])))
        assert got == want, seqs


def _session(config, mix, versions=8):
    from bench import harness

    cfg = json.loads((ROOT / f"bench/configs/{config}.json").read_text())
    traffic = json.loads((ROOT / f"bench/traffic/{mix}.json").read_text())
    return harness.Sessions(cfg, traffic).session(0, 0)[:versions]


@pytest.mark.parametrize("config", ["tpcds_q40_sf1", "tpcds_q50_sf1"])
def test_window_fingerprints_equal_the_serialization_they_replaced(config):
    from repro.core.edits import identity_mapping
    from repro.core.window import VersionPair, WindowTable

    compared = 0
    for mix in ("reexec", "rewrite"):
        vs = _session(config, mix, versions=5)
        for (_, p, _), (_, q, m) in zip(vs, vs[1:]):
            pair = VersionPair(p, q, m or identity_mapping(p, q), "bag")
            table = WindowTable(pair)
            units = range(min(len(pair.units), 11))
            for bits in range(1, 1 << len(units)):
                qp = table.query_pair(table.intern(pair.mask_of(
                    [u for u in units if bits >> u & 1])))
                if qp is not None:
                    assert qp.fingerprint() == _fingerprint_oracle(qp)
                    compared += 1
    assert compared > 300


# -- sessions against the parent commit ------------------------------------------
# Analyst 0's first session of each mix (``bench.harness.Sessions``, versions
# 0..7), submitted in order to one ``VersionChainSession`` with the
# benchmark's EV roster.  Per pair: (version, verdict, certificate digest,
# satisfiability calls).  Obtained by running ``_run_session`` below on the
# commit before the per-pair memo, with ``repro.core.ev.solver.satisfiable`` wrapped to
# count its calls; there every call decided its system.
PARENT = {
    ("tpcds_q40_sf1", "reexec"): [
        (1, "unk", None, 2493),
        (2, "unk", None, 1591),
        (3, "unk", None, 859),
        (4, "unk", None, 532),
        (5, "unk", None, 172),
        (6, "eq", "ca57ecad03515312", 252),
        (7, "eq", "a13fc60543e0249f", 127),
    ],
    ("tpcds_q40_sf1", "rewrite"): [
        (1, "eq", "972d20b43779edb2", 0),
        (2, "eq", "9a07f44e4dff362e", 0),
        (3, "eq", "972d20b43779edb2", 0),
        (4, "eq", "9651def5c3ffd0e8", 4),
        (5, "eq", "10ee99ff99e7ae1b", 6),
        (6, "eq", "960122e7229edd67", 76),
        (7, "eq", "319e60e760df4de4", 30),
    ],
    ("tpcds_q50_sf1", "reexec"): [
        (1, "unk", None, 40923),
        (2, "unk", None, 10313),
        (3, "unk", None, 5666),
        (4, "unk", None, 8),
        (5, "eq", "12e084f1e656977d", 8),
        (6, "unk", None, 76020),
        (7, "unk", None, 21190),
    ],
    ("tpcds_q50_sf1", "rewrite"): [
        (1, "eq", "47294825b12aea3b", 6),
        (2, "eq", "7172c7b07cd02b25", 6),
        (3, "eq", "47294825b12aea3b", 0),
        (4, "eq", "5bee724a60599419", 52),
        (5, "eq", "a892591cb0440189", 10),
        (6, "eq", "efd6e1d354865300", 18),
        (7, "eq", "d6a62da851915db9", 76),
    ],
}
# the first three semantic pairs of the Q50 session whose search runs
# (version 4's ends after two decompositions)
Q50_SEMANTIC = (2, 3, 7)


def _certificate_digest(cert):
    """The certificate's JSON with the lists of each window's payload
    sorted: an identity payload lists operators in set order, which varies
    from one process to the next."""
    if cert is None:
        return None
    d = json.loads(cert.to_json())
    for w in d["windows"]:
        for key, val in w["payload"].items():
            if isinstance(val, list):
                w["payload"][key] = sorted(val, key=json.dumps)
    return hashlib.sha256(json.dumps(d, sort_keys=True).encode()).hexdigest()[:16]


def _run_session(config, mix):
    from repro.api import VeerConfig
    from repro.service import VersionChainSession

    session = VersionChainSession(config=VeerConfig(evs=("equitas", "spes", "udp")))
    out = []
    for k, (_, dag, mapping) in enumerate(_session(config, mix)):
        rep = session.submit(dag, mapping)
        if rep is not None:
            out.append((k, {True: "eq", False: "neq", None: "unk"}[rep.verdict],
                        _certificate_digest(rep.certificate), rep.stats.sat_calls))
    return out


@pytest.fixture(scope="module")
def sessions():
    return {key: _run_session(*key) for key in PARENT}


@pytest.mark.parametrize("key", list(PARENT), ids=lambda k: "-".join(k))
def test_verdicts_and_certificates_equal_the_parents(sessions, key):
    assert [row[:3] for row in sessions[key]] == [row[:3] for row in PARENT[key]]


def test_q50_semantic_pairs_decide_at_most_half_the_parents_satisfiability_problems(sessions):
    key = ("tpcds_q50_sf1", "reexec")
    mine = {k: sat for k, _, _, sat in sessions[key]}
    parent = {k: sat for k, _, _, sat in PARENT[key]}
    for k in Q50_SEMANTIC:
        assert 0 < mine[k] <= parent[k] / 2, (k, mine[k], parent[k])


def test_node_keys_stay_distinct_under_threads():
    """Worker threads of one pair (``Veer(max_workers > 1)``) share its
    memo: structures interned by different threads at the same moment
    never share a key, and one structure has one key."""
    import sys
    import threading

    from repro.core import dag as D

    pair = memo.PairMemo()
    ops = [D.Operator.make(f"f{t}", D.FILTER, pred=None) for t in range(16)]
    own = [[None] * 2000 for _ in ops]
    shared = [[None] * 200 for _ in ops]

    def work(t):
        for j in range(2000):
            own[t][j] = pair.node_key(ops[t], (j,))
            if j < 200:
                shared[t][j] = pair.node_key(ops[0], (-1, j))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in range(len(ops))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert all(row == shared[0] for row in shared)
    keys = [k for row in own for k in row] + shared[0]
    assert len(set(keys)) == len(keys)
