"""Data-plane tests: the jax lowering must be byte-identical to the
reference engine on every operator it claims to lower, and must fall back
per-op (not per-plan) on anything it cannot replicate exactly.

The identity contract is load-bearing: ``table_digest``-keyed stores,
certificates and the reuse frontier never record which plane produced a
table, so a single differing byte would poison every consumer downstream.
"""
import numpy as np
import pytest

from repro.core import dag as D
from repro.core.dag import DataflowDAG, Link, Operator
from repro.core.predicates import LinCmp, LinExpr, Pred
from repro.engine import (
    InMemoryMaterializationStore,
    Table,
    execute,
    tables_identical,
)
from repro import obs
from repro.engine.canon import column_codes, combine_codes, factorize, keyval
from repro.engine.executor import ExecutionPlan
from repro.engine.ops_impl import _keyval, _stable_desc_fix
from repro.engine.ops_impl import execute_op as ref_execute_op
from repro.engine.plane import (
    PlaneError,
    available_planes,
    get_plane,
    register_plane,
)
from repro.service.synthetic import make_chain

jax = pytest.importorskip("jax")


def _sources_for(version, seed=0, n=120):
    rng = np.random.default_rng(seed)
    out = {}
    for sid in version.sources:
        schema = version.ops[sid].get("schema")
        out[sid] = Table(
            {c: rng.integers(-2, 7, n).astype(np.float64) for c in schema},
            list(schema),
        )
    return out


def _assert_planes_identical(dag, sources):
    ref = execute(dag, sources, plane="numpy")
    jx = execute(dag, sources, plane="jax")
    assert set(ref) == set(jx)
    for s in ref:
        assert tables_identical(ref[s], jx[s]), f"sink {s} differs"


def _pipeline(*ops, schema=("a", "b", "c"), sem=D.BAG):
    all_ops = [Operator.make("src", D.SOURCE, schema=schema)]
    links = []
    prev = "src"
    for op in ops:
        all_ops.append(op)
        links.append(Link(prev, op.id))
        prev = op.id
    all_ops.append(Operator.make("sink", D.SINK, semantics=sem))
    links.append(Link(prev, "sink"))
    return DataflowDAG(all_ops, links)


# ---------------------------------------------------------------------------
# plane registry + config plumbing
# ---------------------------------------------------------------------------


def test_registry_lists_both_planes():
    names = available_planes()
    assert "numpy" in names and "jax" in names
    assert get_plane("numpy").name == "numpy"
    assert get_plane("jax").name == "jax"


def test_jax_plane_builds_where_jax_imports():
    # jax imported above: a PlaneError here means the plane broke against
    # the installed jax (as a removed x64 API once did), not a jax-less host
    from repro.engine.plane.jax_plane import JaxPlane

    assert JaxPlane().name == "jax"
    assert get_plane("jax").name == "jax"


def test_get_plane_unknown_raises():
    with pytest.raises(PlaneError, match="numpy"):
        get_plane("not-a-plane")


def test_register_plane_round_trip():
    from repro.engine.plane.numpy_plane import NumpyPlane

    register_plane("numpy2", NumpyPlane)
    try:
        assert "numpy2" in available_planes()
        assert get_plane("numpy2").lowers(None, []) is False
    finally:
        from repro.engine import plane as plane_mod

        plane_mod._REGISTRY.pop("numpy2", None)
        plane_mod._INSTANCES.pop("numpy2", None)


def test_veer_config_rejects_unknown_plane():
    from repro.api.config import ConfigError, VeerConfig

    assert VeerConfig(plane="jax").validate().plane == "jax"
    with pytest.raises(ConfigError, match="plane"):
        VeerConfig(plane="bogus").validate()


def test_workload_config_rejects_unknown_plane():
    from repro.workload.config import WorkloadConfig, WorkloadConfigError

    assert WorkloadConfig(plane="jax").validate().plane == "jax"
    with pytest.raises(WorkloadConfigError, match="plane"):
        WorkloadConfig(plane="bogus").validate()


def test_exec_stats_accounting():
    dag = _pipeline(
        Operator.make("f", D.FILTER, pred=Pred.cmp("a", "<=", 3)),
        Operator.make("di", D.DISTINCT),
    )
    rng = np.random.default_rng(0)
    sources = {
        "src": Table(
            {c: rng.integers(0, 5, 50).astype(np.float64) for c in "abc"},
            ["a", "b", "c"],
        )
    }
    res = ExecutionPlan(dag, sources, plane="numpy").run()
    assert res.stats.plane == "numpy"
    assert res.stats.ops_lowered == 0

    res = ExecutionPlan(dag, sources, plane="jax").run()
    assert res.stats.plane == "jax"
    assert res.stats.ops_lowered >= 2  # filter + distinct at minimum


def _filter_project_join_dag():
    ops = [
        Operator.make("l", D.SOURCE, schema=("k0", "k1", "k2", "x")),
        Operator.make("r", D.SOURCE, schema=("k0", "k1", "k2", "y")),
        Operator.make("f", D.FILTER, pred=Pred.cmp("x", "<=", 5)),
        Operator.make("p", D.PROJECT, cols=(
            ("k0", "k0"), ("k1", "k1"), ("k2", "k2"),
            ("s", LinExpr.make({"x": 2, "k0": 1}, -1)),
        )),
        Operator.make("j", D.JOIN, on=(("k0", "k0"), ("k1", "k1"),
                                       ("k2", "k2")), how="inner"),
        Operator.make("di", D.DISTINCT),
        Operator.make("sink", D.SINK, semantics=D.BAG),
    ]
    links = [Link("l", "f"), Link("f", "p"), Link("p", "j", 0),
             Link("r", "j", 1), Link("j", "di"), Link("di", "sink")]
    return DataflowDAG(ops, links)


def _key_sources(n, key_range, seed=0):
    """Three join keys: drawn from ``key_range`` values each, so 200 rows
    of high-range keys combine into codes far past the dense threshold."""
    rng = np.random.default_rng(seed)

    def side(val):
        cols = {f"k{i}": rng.integers(0, key_range, n).astype(np.float64)
                for i in range(3)}
        if key_range > n:  # let some rows match across the sides
            for i in range(3):
                cols[f"k{i}"][: n // 4] = np.arange(n // 4, dtype=np.float64)
        cols[val] = rng.integers(0, 9, n).astype(np.float64)
        return Table(cols, ["k0", "k1", "k2", val])

    return {"l": side("x"), "r": side("y")}


@pytest.mark.parametrize("key_range,join_on_device", [(4, False), (1 << 40, True)])
def test_ops_on_device_counts_jitted_kernels(key_range, join_on_device):
    """FILTER and PROJECT run jitted kernels; JOIN does only when its
    combined key codes are sparse; DISTINCT is host-vectorized: lowered,
    never on the device."""
    dag = _filter_project_join_dag()
    sources = _key_sources(200, key_range)
    _assert_planes_identical(dag, sources)
    stats = ExecutionPlan(dag, sources, plane="jax").run().stats
    assert stats.ops_lowered == 4
    assert stats.ops_on_device == 2 + join_on_device
    assert ExecutionPlan(dag, sources, plane="numpy").run().stats.ops_on_device == 0


def test_failed_exactness_probe_is_visible(monkeypatch):
    """A kernel that diverges from the reference by one ulp fails the
    probe: the plane says so (flag, first differing value, one warning),
    runs FILTER and PROJECT on the host, and counts nothing on the device
    — and the sinks stay byte-identical."""
    import repro.engine.plane.kernels as rel
    from repro.engine import plane as plane_mod
    from repro.engine.plane.jax_plane import JaxPlane

    exact = rel.build_elementwise

    def divergent(body):
        fn = exact(body)

        def call(*arrs):
            out = fn(*arrs)
            outs = out if isinstance(out, tuple) else (out,)
            outs = tuple(np.nextafter(o, np.inf) if o.dtype.kind == "f" else o
                         for o in outs)
            return outs if isinstance(out, tuple) else outs[0]

        return call

    monkeypatch.setattr(rel, "build_elementwise", divergent)
    plane = JaxPlane()
    register_plane("jax_divergent", lambda: plane)
    try:
        dag = _filter_project_join_dag()
        sources = _key_sources(200, 4)
        with pytest.warns(RuntimeWarning, match="exactness probe failed"):
            res = ExecutionPlan(dag, sources, plane="jax_divergent").run()
        assert plane.exact is False
        assert "row" in plane.probe_mismatch
        assert "reference" in plane.probe_mismatch
        assert res.stats.ops_on_device == 0
        assert res.stats.ops_lowered == 2  # host-vectorized JOIN + DISTINCT
        ref = execute(dag, sources, plane="numpy")
        assert tables_identical(res.results["sink"], ref["sink"])
    finally:
        plane_mod._REGISTRY.pop("jax_divergent", None)
        plane_mod._INSTANCES.pop("jax_divergent", None)


def test_exactness_probe_passes_here():
    plane = get_plane("jax")
    dag = _filter_project_join_dag()
    execute(dag, _key_sources(50, 4), plane="jax")
    assert plane.exact is True and plane.probe_mismatch is None


# ---------------------------------------------------------------------------
# differential identity: randomized chains, all sink semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_seeded_chain_differential(seed):
    rng = np.random.default_rng(seed)
    n_versions = int(rng.integers(2, 5))
    heavy = bool(seed % 2)
    for version in make_chain(n_versions, heavy=heavy):
        _assert_planes_identical(version, _sources_for(version, seed=seed))


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @given(
        n_versions=st.integers(min_value=2, max_value=4),
        seed=st.integers(min_value=0, max_value=10_000),
        heavy=st.booleans(),
    )
    @settings(
        max_examples=8,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    def test_property_chain_differential(n_versions, seed, heavy):
        for version in make_chain(n_versions, heavy=heavy):
            _assert_planes_identical(version, _sources_for(version, seed=seed))

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_chain_differential():
        pass


@pytest.mark.parametrize("sem", [D.SET, D.BAG, D.ORDERED])
def test_differential_all_sink_semantics(sem):
    # identity is bit-level, stronger than any sink semantics — but every
    # semantics flag must survive the plane round trip unchanged
    dag = _pipeline(
        Operator.make("f", D.FILTER, pred=Pred.cmp("a", "<=", 4)),
        Operator.make("di", D.DISTINCT),
        Operator.make("so", D.SORT, keys=(("a", True), ("b", True))),
        sem=sem,
    )
    _assert_planes_identical(dag, _sources_for(dag, seed=len(sem)))


# ---------------------------------------------------------------------------
# edge cases the randomized chains rarely hit
# ---------------------------------------------------------------------------


def _join_dag(how, schema_l=("k", "x"), schema_r=("k", "y"), on=(("k", "k"),)):
    ops = [
        Operator.make("l", D.SOURCE, schema=schema_l),
        Operator.make("r", D.SOURCE, schema=schema_r),
        Operator.make("j", D.JOIN, on=on, how=how),
        Operator.make("sink", D.SINK, semantics=D.ORDERED),
    ]
    links = [Link("l", "j", 0), Link("r", "j", 1), Link("j", "sink")]
    return DataflowDAG(ops, links)


def test_empty_tables_all_ops():
    dag = _pipeline(
        Operator.make("f", D.FILTER, pred=Pred.cmp("a", "<", 1)),
        Operator.make(
            "p", D.PROJECT,
            cols=(("a", "a"), ("s", LinExpr.make({"a": 2, "b": 1}, -1))),
        ),
        Operator.make("ag", D.AGGREGATE, group_by=("a",),
                      aggs=(("sum", "s", "ss"), ("count", "*", "n"))),
        Operator.make("so", D.SORT, keys=(("ss", True), ("a", True))),
        sem=D.ORDERED,
    )
    empty = {"src": Table({c: np.array([]) for c in "abc"}, ["a", "b", "c"])}
    _assert_planes_identical(dag, empty)

    for how in ("inner", "left_outer"):
        jd = _join_dag(how)
        _assert_planes_identical(jd, {
            "l": Table({"k": np.array([]), "x": np.array([])}, ["k", "x"]),
            "r": Table({"k": np.array([]), "y": np.array([])}, ["k", "y"]),
        })


def test_left_outer_all_unmatched():
    dag = _join_dag("left_outer")
    sources = {
        "l": Table({"k": np.arange(5.0), "x": np.arange(5.0)}, ["k", "x"]),
        "r": Table({"k": np.arange(100.0, 103.0),
                    "y": np.arange(3.0)}, ["k", "y"]),
    }
    _assert_planes_identical(dag, sources)
    out = execute(dag, sources, plane="jax")["sink"]
    assert len(out) == 5 and np.isnan(np.asarray(out.cols["y"])).all()


def test_duplicate_key_join_blowup():
    # every key matches every right row with that key: 20x20 per key value
    rng = np.random.default_rng(7)
    sources = {
        "l": Table({"k": np.repeat([1.0, 2.0], 20),
                    "x": rng.integers(0, 9, 40).astype(np.float64)},
                   ["k", "x"]),
        "r": Table({"k": np.repeat([2.0, 3.0], 20),
                    "y": rng.integers(0, 9, 40).astype(np.float64)},
                   ["k", "y"]),
    }
    for how in ("inner", "left_outer"):
        _assert_planes_identical(_join_dag(how), sources)
    out = execute(_join_dag("inner"), sources, plane="jax")["sink"]
    assert len(out) == 20 * 20


def test_nan_and_negative_zero_join_keys():
    # NaN keys never match (fresh dict key per row); -0.0 joins +0.0
    sources = {
        "l": Table({"k": np.array([np.nan, -0.0, 1.0, np.nan]),
                    "x": np.arange(4.0)}, ["k", "x"]),
        "r": Table({"k": np.array([np.nan, 0.0, 1.0]),
                    "y": np.arange(3.0)}, ["k", "y"]),
    }
    for how in ("inner", "left_outer"):
        _assert_planes_identical(_join_dag(how), sources)


def test_sparse_code_join_uses_jitted_probe():
    """Four high-cardinality key columns push the combined (uncompressed)
    code range past the dense-lookup threshold, forcing the jitted
    stable-argsort/searchsorted probe — both probes must agree."""
    rng = np.random.default_rng(9)
    n = 64
    cols = {f"k{i}": rng.permutation(n).astype(np.float64) for i in range(4)}
    lx = dict(cols, x=np.arange(float(n)))
    # right shares half its rows' keys with the left
    ridx = rng.permutation(n)[: n // 2]
    rcols = {f"k{i}": cols[f"k{i}"][ridx] for i in range(4)}
    ry = dict(rcols, y=np.arange(float(n // 2)))
    on = tuple((f"k{i}", f"k{i}") for i in range(4))
    schema_l = tuple(lx)
    schema_r = tuple(ry)
    for how in ("inner", "left_outer"):
        dag = _join_dag(how, schema_l=schema_l, schema_r=schema_r, on=on)
        _assert_planes_identical(dag, {
            "l": Table(lx, list(schema_l)),
            "r": Table(ry, list(schema_r)),
        })


def test_sparse_code_join_over_several_probe_blocks():
    """A sparse-code join whose right side fills many probe blocks, with
    runs of equal keys both inside a block and longer than one."""
    from repro.engine.plane.jax_plane import probe_layout
    from repro.engine.plane.kernels import pow2_bucket

    rng = np.random.default_rng(14)
    pool = rng.integers(0, 1 << 40, (400, 3)).astype(np.float64)
    nl, nr, run = 3000, 1500, 150
    block, n_blocks, _ = probe_layout(pow2_bucket(nl), pow2_bucket(nr))
    assert n_blocks > 8 and run > 2 * block
    lkeys = pool[rng.integers(0, 400, nl)]
    rkeys = pool[rng.integers(200, 600, nr) % 400]
    rkeys[:run] = pool[7]
    lcols = {f"k{i}": lkeys[:, i] for i in range(3)}
    rcols = {f"k{i}": rkeys[:, i] for i in range(3)}
    lx = dict(lcols, x=np.arange(float(nl)))
    ry = dict(rcols, y=np.arange(float(nr)))
    on = tuple((f"k{i}", f"k{i}") for i in range(3))
    sources = {"l": Table(lx, list(lx)), "r": Table(ry, list(ry))}
    for how in ("inner", "left_outer"):
        dag = _join_dag(how, schema_l=tuple(lx), schema_r=tuple(ry), on=on)
        _assert_planes_identical(dag, sources)
        assert ExecutionPlan(dag, sources, plane="jax").run().stats.ops_on_device == 1


_CODE_SENTINEL = 1 << 62  # the plane pads both probe operands with it


def _padded(keys, bucket):
    out = np.full(bucket, _CODE_SENTINEL, dtype=np.int64)
    out[: len(keys)] = keys
    return out


def _probe_case(name):
    """``(lk, sr)`` for the blocked probe: left keys, sorted right keys."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "run_across_blocks":
        # B = 16 for 200 right keys; 70 equal keys span five blocks
        sr = np.sort(np.concatenate([rng.integers(0, 50, 130), np.full(70, 25)]))
        return np.arange(-2, 53), sr
    if name == "fences_and_ends":
        sr = np.sort(rng.choice(1 << 40, 1000, replace=False))
        fences = sr[:: 32]  # B = 32 for 1000 right keys
        ends = [sr[0] - 1, sr[-1] + 1, np.iinfo(np.int64).min, np.iinfo(np.int64).max]
        return np.concatenate([fences, fences - 1, fences + 1, ends]), sr
    if name == "sentinel_tails":
        sr = _padded(np.sort(rng.integers(0, 40, 100)), 128)
        return _padded(rng.integers(-1, 42, 37), 64), sr
    if name == "odd_length":
        return rng.integers(-1, 300, 500), np.sort(rng.integers(0, 300, 777))
    if name == "single_right_key":
        return np.array([4, 5, 6, 5, _CODE_SENTINEL]), np.array([5])
    if name == "near_2_61":
        top = 1 << 61
        sr = np.sort(rng.integers(top - 100, top, 300))
        return np.concatenate([rng.integers(top - 103, top + 3, 200), [_CODE_SENTINEL]]), sr
    assert name == "chunk_remainder"
    # 4097 right keys: B = 128, a chunk of 2^22 / 128 = 32768 left keys
    sr = np.sort(rng.integers(0, 5000, 4097))
    return rng.integers(-1, 5001, 40000), sr


@pytest.mark.parametrize("case", [
    "run_across_blocks", "fences_and_ends", "sentinel_tails", "odd_length",
    "single_right_key", "near_2_61", "chunk_remainder",
])
def test_blocked_join_probe_matches_searchsorted(case):
    """The device probe's windows equal ``np.searchsorted``'s on both sides,
    element for element."""
    import jax.numpy as jnp

    from repro.engine.plane.jax_plane import _join_probe_body, probe_layout

    lk, sr = (np.asarray(a, dtype=np.int64) for a in _probe_case(case))
    block, _, chunk = probe_layout(len(lk), len(sr))
    if case == "run_across_blocks":
        assert block == 16
    if case == "chunk_remainder":
        assert len(lk) % chunk != 0 and len(lk) > chunk
    with jax.enable_x64(True):
        lo, hi = jax.jit(_join_probe_body)(jnp.asarray(lk), jnp.asarray(sr))
    np.testing.assert_array_equal(np.asarray(lo), np.searchsorted(sr, lk, side="left"))
    np.testing.assert_array_equal(np.asarray(hi), np.searchsorted(sr, lk, side="right"))


def test_single_group_aggregate():
    dag = _pipeline(
        Operator.make("ag", D.AGGREGATE, group_by=("a",),
                      aggs=(("sum", "b", "sb"), ("avg", "c", "ac"),
                            ("min", "b", "mb"), ("max", "c", "xc"),
                            ("count", "*", "n"))),
        sem=D.ORDERED,
    )
    rng = np.random.default_rng(3)
    sources = {"src": Table(
        {"a": np.full(64, 2.0),
         "b": rng.integers(-5, 5, 64).astype(np.float64),
         "c": rng.integers(-5, 5, 64).astype(np.float64)},
        ["a", "b", "c"],
    )}
    _assert_planes_identical(dag, sources)
    # and the global (no group_by) form
    dag2 = _pipeline(
        Operator.make("ag", D.AGGREGATE, group_by=(),
                      aggs=(("sum", "b", "sb"), ("count", "*", "n"))),
        sem=D.ORDERED,
    )
    _assert_planes_identical(dag2, sources)


def test_left_outer_pad_upcasts_int_to_float64():
    """Satellite regression: the np.nan pad on unmatched left rows upcasts
    integer right columns to float64 — the canonical bytes both planes must
    agree on (an int-preserving pad would change every digest downstream)."""
    dag = _join_dag("left_outer")
    sources = {
        "l": Table({"k": np.arange(4.0), "x": np.arange(4.0)}, ["k", "x"]),
        "r": Table({"k": np.array([0.0, 2.0]),
                    "y": np.array([10, 20], dtype=np.int64)}, ["k", "y"]),
    }
    ref = execute(dag, sources, plane="numpy")["sink"]
    jx = execute(dag, sources, plane="jax")["sink"]
    assert tables_identical(ref, jx)
    assert np.asarray(ref.cols["y"]).dtype == np.float64
    assert np.asarray(jx.cols["y"]).dtype == np.float64


def test_object_column_falls_back_per_op():
    """A plan mixing object and numeric columns executes mixed-plane: the
    jax plane lowers what it can and delegates the rest, byte-identically."""
    obj = np.array(["u", "v", "w", "u", "v", "w"], dtype=object)
    src = Table({"a": np.array([3.0, 1.0, 2.0, 3.0, 1.0, 2.0]), "t": obj},
                ["a", "t"])
    dag = _pipeline(
        Operator.make("f", D.FILTER, pred=Pred.cmp("a", "<=", 2)),
        Operator.make("di", D.DISTINCT),
        schema=("a", "t"),
        sem=D.BAG,
    )
    _assert_planes_identical(dag, {"src": src})
    plane = get_plane("jax")
    di = dag.ops["di"]
    assert not plane.lowers(di, [src])  # object column -> reference


def test_adversarial_float_filter_and_project():
    """Fractional coefficients + near-boundary values: the two-program
    multiply/accumulate split must agree with the scalar reference even
    where an FMA-contracted evaluation would flip a comparison."""
    rng = np.random.default_rng(11)
    vals = np.concatenate([
        rng.uniform(-1e6, 1e6, 2000),
        rng.integers(-3, 4, 500).astype(np.float64) / 3.0,
        np.array([0.1, 0.2, 0.3, 1e-9, -1e-9, 1e15, -1e15]),
    ])
    rng.shuffle(vals)
    n = len(vals)
    src = Table(
        {"a": vals, "b": np.roll(vals, 7), "c": np.roll(vals, 13)},
        ["a", "b", "c"],
    )
    from fractions import Fraction

    dag = _pipeline(
        Operator.make("f", D.FILTER, pred=Pred.of(LinCmp(
            LinExpr.make({"a": Fraction(5, 2), "b": Fraction(-7, 4)},
                         Fraction(1, 3)), "<="))),
        Operator.make("p", D.PROJECT, cols=(
            ("a", "a"),
            ("s", LinExpr.make({"a": Fraction(1, 3), "b": 2,
                                "c": Fraction(-1, 7)}, -0.5)),
        )),
        sem=D.BAG,
    )
    _assert_planes_identical(dag, {"src": src})
    assert n > 0


def test_sort_descending_and_mixed_directions():
    # descending keys take the reference path (the plane lowers only
    # all-ascending sorts); both planes must still agree end-to-end
    rng = np.random.default_rng(5)
    src = Table(
        {"a": rng.integers(0, 4, 200).astype(np.float64),
         "b": rng.integers(0, 4, 200).astype(np.float64),
         "c": np.arange(200.0)},
        ["a", "b", "c"],
    )
    for keys in ((("a", True), ("b", True)),
                 (("a", False), ("b", True)),
                 (("a", True), ("b", False))):
        dag = _pipeline(Operator.make("so", D.SORT, keys=keys), sem=D.ORDERED)
        _assert_planes_identical(dag, {"src": src})


# ---------------------------------------------------------------------------
# session + certificates on the jax plane
# ---------------------------------------------------------------------------


def test_session_on_jax_plane_certificates_replay():
    from repro.api import VeerConfig
    from repro.api.registry import default_registry
    from repro.service import VersionChainSession

    chain = make_chain(3, heavy=True)
    sources = _sources_for(chain[0], seed=0, n=80)
    truth = [execute(v, sources) for v in chain]  # reference plane

    session = VersionChainSession(
        config=VeerConfig(plane="jax"),
        materialization_store=InMemoryMaterializationStore(),
    )
    reports = [session.submit(v, sources=sources) for v in chain]
    registry = default_registry()
    lowered = 0
    for k, (r, full) in enumerate(zip(reports, truth)):
        for s, table in full.items():
            assert tables_identical(r.results[s], table)
        if r.exec_stats:
            assert r.exec_stats.plane == "jax"
            lowered += r.exec_stats.ops_lowered
        if k and r.certified:
            assert r.certificate.replay(registry, chain[k - 1], chain[k]).ok
    assert lowered > 0


# ---------------------------------------------------------------------------
# satellite: vectorized _stable_desc_fix
# ---------------------------------------------------------------------------


def _desc_fix_scalar(sorted_vals, order_):
    """The pre-vectorization reference: walk runs of keyval-equal values."""
    n = len(order_)
    out = order_.copy()
    i = 0
    while i < n:
        j = i
        while j + 1 < n and _keyval(sorted_vals[j + 1]) == _keyval(sorted_vals[i]):
            j += 1
        out[i:j + 1] = order_[i:j + 1][::-1]
        i = j + 1
    return out


@pytest.mark.parametrize("seed", range(6))
def test_stable_desc_fix_matches_scalar_walk(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(0, 120))
    vals = rng.integers(-3, 4, n).astype(np.float64)
    vals[rng.random(n) < 0.1] = np.nan
    vals[rng.random(n) < 0.1] = -0.0
    order_ = np.argsort(vals, kind="stable")
    sorted_vals = vals[order_]
    got = _stable_desc_fix(sorted_vals, order_)
    want = _desc_fix_scalar(sorted_vals, order_)
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# canon: code columns
# ---------------------------------------------------------------------------


def test_column_codes_fast_and_slow_paths_agree():
    # values 1e-10 apart share a 9-digit rounding -> the keyval dict loop
    # must collapse them; integer-spaced values take the identity fast path
    close = np.array([1.0, 1.0 + 1e-10, 2.0, 1.0 + 1e-10, 5.0])
    codes = column_codes(close, nan_distinct=False)
    assert codes[0] == codes[1] == codes[3]
    assert len(set(codes.tolist())) == 3

    spread = np.array([3.0, -1.0, 3.0, 7.0])
    codes = column_codes(spread, nan_distinct=False)
    assert codes[0] == codes[2] and len(set(codes.tolist())) == 3


def test_column_codes_nan_semantics():
    arr = np.array([np.nan, 1.0, np.nan, -0.0, 0.0])
    distinct = column_codes(arr, nan_distinct=True)
    assert distinct[0] != distinct[2]  # each NaN its own dict key
    assert distinct[3] == distinct[4]  # -0.0 == 0.0
    collapsed = column_codes(arr, nan_distinct=False)
    assert collapsed[0] == collapsed[2]  # repr-keyed: all NaNs print "nan"


def _sort_codes_oracle(arr, *, nan_distinct):
    """``column_codes`` as it was before the sort-free path: ``np.unique``,
    the ``keyval`` remap of the uniques where two may share a rounding,
    NaN rows numbered after the uniques."""
    arr = np.asarray(arr)
    if arr.size == 0:
        return np.zeros(0, dtype=np.int64)
    uniq, inv = np.unique(arr, return_inverse=True)
    inv = inv.reshape(-1).astype(np.int64)
    merge_possible = False
    n_slots = len(uniq)
    if arr.dtype.kind == "f":
        fu = uniq[~np.isnan(uniq)] if np.isnan(uniq[-1]) else uniq
        merge_possible = len(fu) > 1 and float(np.min(np.diff(fu))) <= 1e-8
    if not merge_possible:
        codes = inv
    else:
        slots: dict = {}
        remap = np.empty(len(uniq), dtype=np.int64)
        for i, u in enumerate(uniq):
            remap[i] = slots.setdefault(keyval(u), len(slots))
        codes = remap[inv]
        n_slots = len(slots)
    if arr.dtype.kind == "f":
        nan_mask = np.isnan(arr)
        if nan_mask.any() and nan_distinct:
            codes[nan_mask] = np.int64(n_slots) + np.arange(
                int(nan_mask.sum()), dtype=np.int64
            )
    return codes


def _codes_case(name):
    """``(column, sorts)``: a key column and whether ``column_codes`` must
    take the ``np.unique`` sort for it."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "shuffled_repeats":
        return rng.permutation(np.repeat(rng.integers(1, 400, 300), 3)).astype(np.float64), False
    if name == "negatives_and_zeros":
        return np.array([-3.0, 0.0, -0.0, 5.0, -3.0, -0.0, 2.0, -1.0]), False
    if name == "only_negative_zero":
        return np.array([-0.0, 1.0, -0.0, -2.0]), False
    if name in ("nan_front", "nan_middle", "nan_end"):
        col = rng.integers(-20, 20, 60).astype(np.float64)
        at = {"nan_front": [0, 1], "nan_middle": [29, 30, 45], "nan_end": [58, 59]}[name]
        col[at] = np.nan
        return col, False
    if name == "all_nan":
        return np.full(5, np.nan), False
    if name == "single_value":
        return np.full(7, 42.0), False
    if name == "empty":
        return np.zeros(0), False
    if name == "int64":
        return rng.integers(-(1 << 40), -(1 << 40) + 200, 100), False
    if name == "uint8":
        return rng.integers(0, 256, 100).astype(np.uint8), False
    if name == "bool":
        return rng.random(50) < 0.5, False
    if name == "float32":
        return rng.integers(-50, 50, 40).astype(np.float32), False
    if name == "far_from_zero":
        return 1e6 + rng.integers(0, 90, 30).astype(np.float64), False
    if name == "near_2_52":  # a bitmap over [0, max] would take petabytes
        return 2.0**52 + rng.integers(0, 90, 30).astype(np.float64), False
    if name in ("range_at_4n", "range_over_4n"):
        col = rng.integers(0, 40, 25).astype(np.float64)
        col[[3, 11]] = (-5.0, -5.0 + 100 + (name == "range_over_4n"))
        return col, name == "range_over_4n"
    if name == "plus_inf":
        return np.array([1.0, np.inf, 2.0, 1.0]), True
    if name == "minus_inf":
        return np.array([1.0, -np.inf, np.nan, 1.0]), True
    if name == "beyond_2_53":
        return np.array([2.0**53 + 2, 2.0**53 + 4, 2.0**53 + 2]), True
    if name == "near_one":
        return np.array([1.0, 1.0 + 1e-10, 2.0, 1.0 + 1e-10]), True
    assert name == "halves"
    return np.array([0.5, 1.5, 0.5, -2.5]), True


@pytest.mark.parametrize("nan_distinct", [True, False])
@pytest.mark.parametrize("case", [
    "shuffled_repeats", "negatives_and_zeros", "only_negative_zero", "nan_front",
    "nan_middle", "nan_end", "all_nan", "single_value", "empty", "int64", "uint8",
    "bool", "float32", "far_from_zero", "near_2_52", "range_at_4n", "range_over_4n", "plus_inf", "minus_inf",
    "beyond_2_53", "near_one", "halves",
])
def test_column_codes_equal_the_sort_based_oracle(case, nan_distinct):
    col, sorts = _codes_case(case)
    codes, sorted_ = factorize(col, nan_distinct=nan_distinct)
    want = _sort_codes_oracle(col, nan_distinct=nan_distinct)
    assert codes.dtype == np.int64 and np.array_equal(codes, want)
    assert np.array_equal(column_codes(col, nan_distinct=nan_distinct), want)
    assert sorted_ == sorts


def _recorded_codes_spans(monkeypatch):
    """The attributes of every ``veer.plane.join.codes`` span opened from
    now on, as the span ends."""
    seen = []

    class _Rec:
        def __init__(self, attrs):
            self.attrs = attrs

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            seen.append(self.attrs)

        def set_metadata(self, **attrs):
            self.attrs.update(attrs)

    real = obs.span

    def span(name, **attrs):
        return _Rec(dict(attrs)) if name == "veer.plane.join.codes" else real(name, **attrs)

    monkeypatch.setattr(obs, "span", span)
    return seen


@pytest.mark.parametrize("fractional", [False, True])
def test_sparse_three_key_join_on_rank_codes(monkeypatch, fractional):
    """Three narrow integral key columns, with NaN and signed-zero keys,
    whose combined codes are sparse enough for the device probe: the jax
    plane's inner and left-outer joins equal the numpy plane's, and the
    codes span counts the key columns and those that took the sort (one
    where a column holds fractions)."""
    rng = np.random.default_rng(16)
    nl, nr = 3000, 1500
    pool = rng.integers(-150, 150, (500, 3)).astype(np.float64)
    pool[:40, 0] = -0.0
    pool[40:80, 1] = 0.0
    pool[80:90, 2] = np.nan
    if fractional:
        pool[:, 1] += 0.25
    lkeys = pool[rng.integers(0, 500, nl)]
    rkeys = pool[rng.integers(250, 750, nr) % 500]
    lx = dict({f"k{i}": lkeys[:, i] for i in range(3)}, x=np.arange(float(nl)))
    ry = dict({f"k{i}": rkeys[:, i] for i in range(3)}, y=np.arange(float(nr)))
    on = tuple((f"k{i}", f"k{i}") for i in range(3))
    sources = {"l": Table(lx, list(lx)), "r": Table(ry, list(ry))}
    seen = _recorded_codes_spans(monkeypatch)
    for how in ("inner", "left_outer"):
        dag = _join_dag(how, schema_l=tuple(lx), schema_r=tuple(ry), on=on)
        _assert_planes_identical(dag, sources)
        assert ExecutionPlan(dag, sources, plane="jax").run().stats.ops_on_device == 1
    assert seen and all(s["keys"] == 3 and s["sorted"] == int(fractional)
                        and s["device"] == 1 for s in seen)


def test_combine_codes_overflow_fold():
    # per-column maxima large enough that folding without compression
    # would overflow int64: the fold must re-unique, not wrap around
    rng = np.random.default_rng(1)
    big = np.int64(1) << 40
    a = rng.integers(0, 5, 64).astype(np.int64) * (big // 5)
    b = rng.integers(0, 5, 64).astype(np.int64) * (big // 5)
    c = rng.integers(0, 5, 64).astype(np.int64) * (big // 5)
    out = combine_codes([a, b, c])
    ref_keys = {}
    ref = np.array([ref_keys.setdefault((x, y, z), len(ref_keys))
                    for x, y, z in zip(a, b, c)])
    # same equality structure as tuple dict keys
    assert len(np.unique(out)) == len(ref_keys)
    for i in range(len(out)):
        for j in range(len(out)):
            assert (out[i] == out[j]) == (ref[i] == ref[j])


# ---------------------------------------------------------------------------
# kernels: jit bucket padding
# ---------------------------------------------------------------------------


def test_build_elementwise_bucket_padding_is_exact():
    from repro.engine.plane.kernels import build_elementwise

    def body(x, y):
        return x + y, (x + y) <= 2.0

    fn = build_elementwise(body)
    for n in (0, 1, 7, 1024, 1025, 4097):
        rng = np.random.default_rng(n)
        x = rng.integers(-3, 4, n).astype(np.float32)
        y = rng.integers(-3, 4, n).astype(np.float32)
        s, m = fn(x, y)
        assert len(s) == len(m) == n
        assert np.array_equal(s, x + y)
        assert np.array_equal(m, (x + y) <= 2.0)


def test_pow2_bucket():
    from repro.engine.plane.kernels import pow2_bucket

    assert pow2_bucket(0) == 1
    assert pow2_bucket(1) == 1
    assert pow2_bucket(2) == 2
    assert pow2_bucket(3) == 4
    assert pow2_bucket(1024) == 1024
    assert pow2_bucket(1025) == 2048
