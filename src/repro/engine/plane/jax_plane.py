"""The vectorized JAX plane: jitted/device lowerings of the hot operators.

Byte-identity is the whole game: this plane must reproduce the reference
engine's per-row dict/loop semantics **bit-for-bit** (content digests,
materialization keys, certificates and the reuse frontier all hash the
canonical numpy bytes).  Three design rules make that possible:

1. **Dict-key canonicalization is unique-compressed, never re-derived.**
   Join keys, aggregate groups and distinct rows are factorized with
   ``repro.engine.canon.column_codes`` — ranks off a presence bitmap for
   integral columns of narrow range, else ``np.unique`` for the vectorized
   part and the real Python ``round``/dict-equality applied only to the
   unique values — so rounded-float collapse, ``-0.0 == 0.0`` and
   NaN-identity semantics match the reference exactly.

2. **Float arithmetic is split so XLA cannot FMA-contract it.**  XLA CPU
   rewrites ``a*b + c`` into a fused multiply-add whose 1-ulp-different
   results would silently change sink bytes (and ``optimization_barrier``
   does not stop it).  Every fused filter/project kernel is therefore two
   programs: a *multiply* program whose products are all outputs (a
   standalone multiply must be correctly rounded), and an
   *accumulate/compare/combine* program containing no multiplies at all —
   nothing left to contract, so it is exact by construction.  A one-time
   self-probe (``values_exact``) verifies this on adversarial data at first
   use.  If the backend diverges, the plane records the first differing
   value (``probe_mismatch``), warns once, and runs FILTER and PROJECT on
   the host for the rest of the process.  Errors from compiling or running
   a kernel are never caught: they propagate to the caller.

3. **Everything unsupported falls back per-operator** to the reference
   plane (object-dtype columns, string/opaque predicates, UDFs, ...) —
   mixed-plane execution: the chain always runs, bytes always match.

Lowering map (see ``docs/DATA_PLANE.md`` for the rationale per row):

  FILTER      fused two-program predicate kernel (LinCmp trees; StrEq /
              NonLinearAtom masks evaluated host-side and fused in)
  PROJECT     fused two-program linear-expression kernel
  JOIN        joint unique-compression of key columns; dense codes probe
              a host bincount table, sparse codes a host stable argsort
              plus a jitted two-level blocked search (fences, then one
              gathered block per key); host np.repeat expansion
  AGGREGATE   group codes + stable argsort into contiguous segments;
              per-group reductions on contiguous float64 slices (same
              pairwise summation as the reference)
  DISTINCT    per-column codes (NaN collapsed) -> first-occurrence rows
  SORT        ``np.lexsort`` for all-ascending numeric keys (the unique
              stable permutation); descending delegates to the reference,
              whose run-flip is vectorized in ``ops_impl``
  UNNEST      vectorized identity for scalar numeric columns
  DICT/CLS    unique-compress + per-unique hash/membership, scattered back
  others      reference (already vectorized or inherently opaque)
"""

from __future__ import annotations

import threading
import warnings
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core import dag as D
from repro.core.predicates import LinCmp, NonLinearAtom, Pred, StrEq
from repro.engine.canon import column_codes, combine_codes, factorize, keyval
from repro.engine.ops_impl import eval_linexpr, eval_pred
from repro.engine.plane.base import DataPlane, PlaneError
from repro.engine.plane.numpy_plane import NumpyPlane
from repro.engine.table import Table

_AGG_FNS = ("count", "sum", "min", "max", "avg")


def _modules():
    """Lazy jax import: (jax, jnp) or PlaneError if jax is not installed."""
    try:
        import jax
        import jax.numpy as jnp
    except ImportError as e:  # pragma: no cover - exercised on jax-less hosts
        raise PlaneError(f"jax backend unavailable: {e}") from e
    return jax, jnp


def _x64():
    """Scope in which the plane's kernels trace and run in 64 bits."""
    jax, _ = _modules()
    return jax.enable_x64(True)


class _PredPlan:
    """Compiled two-program predicate kernel (see module docstring)."""

    __slots__ = ("prods_spec", "host_atoms", "lin_cols", "mul", "mask",
                 "mul_body", "mask_body")

    def __init__(self, prods_spec, host_atoms, lin_cols, mul, mask,
                 mul_body, mask_body):
        self.prods_spec = prods_spec
        self.host_atoms = host_atoms
        self.lin_cols = lin_cols
        self.mul = mul
        self.mask = mask
        self.mul_body = mul_body
        self.mask_body = mask_body


class _ProjPlan:
    """Compiled two-program projection kernel."""

    __slots__ = ("prods_spec", "items", "lin_cols", "mul", "val",
                 "mul_body", "val_body")

    def __init__(self, prods_spec, items, lin_cols, mul, val,
                 mul_body, val_body):
        self.prods_spec = prods_spec
        self.items = items
        self.lin_cols = lin_cols
        self.mul = mul
        self.val = val
        self.mul_body = mul_body
        self.val_body = val_body


_NO_PLAN = object()


class JaxPlane(DataPlane):
    name = "jax"

    def __init__(self):
        _modules()  # fail fast with PlaneError when jax is missing
        self._ref = NumpyPlane()
        self._pred_plans: Dict[str, object] = {}
        self._proj_plans: Dict[str, object] = {}
        self._join_probe = None
        self._probe_lock = threading.Lock()
        self._dispatch = threading.local()
        #: exactness probe outcome: None until it runs, then True/False
        self.exact: Optional[bool] = None
        #: first value where a probed kernel differed from the reference
        self.probe_mismatch: Optional[str] = None

    def device_dispatches(self) -> int:
        return getattr(self._dispatch, "n", 0)

    def _dispatched(self) -> None:
        self._dispatch.n = self.device_dispatches() + 1

    # -- protocol -------------------------------------------------------------
    def lowers(self, op: D.Operator, inputs: List[Table]) -> bool:
        t = op.op_type
        if t in (D.FILTER, D.PROJECT) and not self.values_exact():
            return False
        try:
            if t == D.FILTER:
                plan = self._pred_plan(op.get("pred"))
                return plan is not None and _numeric(inputs[0], plan.lin_cols)
            if t == D.PROJECT:
                plan = self._proj_plan(op.get("cols"))
                return plan is not None and _numeric(inputs[0], plan.lin_cols)
            if t == D.JOIN:
                left, right = inputs
                on = op.get("on")
                return all(
                    left.cols[lc].dtype != object
                    and right.cols[rc].dtype != object
                    for lc, rc in on
                )
            if t == D.AGGREGATE:
                src = inputs[0]
                group_by = list(op.get("group_by", ()))
                aggs = op.get("aggs")
                if not _numeric(src, group_by):
                    return False
                for fn, c, _ in aggs:
                    if fn not in _AGG_FNS:
                        return False
                    if c == "*":
                        if fn != "count":
                            return False
                    elif c not in src.cols or src.cols[c].dtype == object:
                        return False
                return True
            if t == D.DISTINCT:
                return all(
                    inputs[0].cols[c].dtype != object for c in inputs[0].order
                )
            if t == D.SORT:
                keys = list(op.get("keys"))
                return bool(keys) and all(asc for _, asc in keys) and _numeric(
                    inputs[0], [c for c, _ in keys]
                )
            if t == D.UNNEST:
                return inputs[0].cols[op.get("col")].dtype != object
            if t == D.DICT_MATCHER:
                return inputs[0].cols[op.get("col")].dtype != object
            if t in (D.CLASSIFIER, D.SENTIMENT):
                col = inputs[0].cols[op.get("col")]
                return col.dtype != object and not _mixed_zero_signs(col)
            return False
        except (KeyError, TypeError, AttributeError):
            return False

    def execute_op(self, op: D.Operator, inputs: List[Table]) -> Table:
        # every operator the executor and the delta engine run passes here:
        # the one span per operator, named by its type
        with obs.span(f"veer.exec.{op.op_type}", op=op.id,
                      rows_in=sum(len(t) for t in inputs)):
            return self._execute_op(op, inputs)

    def _execute_op(self, op: D.Operator, inputs: List[Table]) -> Table:
        if not self.lowers(op, inputs):
            return self._ref.execute_op(op, inputs)
        t = op.op_type
        if t == D.FILTER:
            return self._filter(op, inputs)
        if t == D.PROJECT:
            return self._project(op, inputs)
        if t == D.JOIN:
            return self._join(op, inputs)
        if t == D.AGGREGATE:
            return self._aggregate(op, inputs)
        if t == D.DISTINCT:
            return self._distinct(op, inputs)
        if t == D.SORT:
            return self._sort(op, inputs)
        if t == D.UNNEST:
            return self._unnest(op, inputs)
        if t == D.DICT_MATCHER:
            return self._dict_matcher(op, inputs)
        if t in (D.CLASSIFIER, D.SENTIMENT):
            return self._classifier(op, inputs)
        raise AssertionError(f"lowers/execute_op disagree on {t}")

    # -- FILTER / PROJECT: fused two-program kernels --------------------------
    def values_exact(self) -> bool:
        """One-time self-probe: the compiled two-program kernels must be
        bit-identical to the reference on adversarial (uniform-float) data.
        Exact by construction on a correct backend; a divergence (e.g. a
        backend that FMA-contracts across program boundaries, or a float64
        emulation that rounds differently) sends FILTER and PROJECT to the
        host for the whole process, with ``exact``/``probe_mismatch`` set
        and one ``RuntimeWarning``."""
        if self.exact is None:
            with self._probe_lock:
                if self.exact is None:
                    self.probe_mismatch = self._run_exactness_probe()
                    self.exact = self.probe_mismatch is None
                    if not self.exact:
                        warnings.warn(
                            "jax plane exactness probe failed "
                            f"({self.probe_mismatch}); FILTER and PROJECT "
                            "run on the host",
                            RuntimeWarning,
                            stacklevel=2,
                        )
        return self.exact

    def _run_exactness_probe(self) -> Optional[str]:
        """None when every probed value is bit-identical, else a
        description of the first one that is not."""
        from fractions import Fraction

        from repro.core.predicates import LinExpr

        rng = np.random.default_rng(0x5EED)
        n = 4096
        t = Table(
            {c: rng.uniform(-1e6, 1e6, n) for c in ("a", "b", "c")},
            ["a", "b", "c"],
        )
        e1 = LinExpr.make({"a": Fraction(5, 2), "b": Fraction(-7, 4)}, 1)
        e2 = LinExpr.make({"b": Fraction(1, 3), "c": 2}, Fraction(-1, 2))
        pred = Pred.and_(Pred.of(LinCmp(e1, "<=")), Pred.of(LinCmp(e2, "<")))
        plan = self._compile_pred(pred)
        got = self._eval_pred_plan(plan, t)
        bad = _first_mismatch("filter mask", got, eval_pred(pred, t))
        if bad:
            return bad
        cols = (("x", e1), ("y", e2), ("b", "b"))
        pplan = self._compile_proj(cols)
        proj = self._eval_proj_plan(pplan, t)
        for name, expr in cols:
            want = t.cols[expr] if isinstance(expr, str) else eval_linexpr(expr, t)
            bad = _first_mismatch(f"project column {name}", proj.cols[name], want)
            if bad:
                return bad
        return None

    def _pred_plan(self, pred: Pred):
        if not self.values_exact():
            return None
        key = repr(pred)
        plan = self._pred_plans.get(key)
        if plan is None:
            plan = self._compile_pred(pred) or _NO_PLAN
            self._pred_plans[key] = plan
        return None if plan is _NO_PLAN else plan

    def _compile_pred(self, pred: Pred) -> Optional[_PredPlan]:
        _, jnp = _modules()
        from repro.engine.plane.kernels import build_elementwise

        lin_atoms: List[LinCmp] = []
        host_atoms: List = []
        supported = True

        def scan(p: Pred):
            nonlocal supported
            if p.kind in ("true", "false"):
                return
            if p.kind in ("and", "or", "not"):
                for c in p.children:
                    scan(c)
                return
            if p.kind == "atom":
                a = p.atom
                if isinstance(a, LinCmp) and a.expr.coeffs:
                    lin_atoms.append(a)
                elif isinstance(a, (LinCmp, StrEq, NonLinearAtom)):
                    host_atoms.append(a)
                else:
                    supported = False
                return
            supported = False

        scan(pred)
        if not supported or not lin_atoms:
            return None

        prods_spec: List[Tuple[str, float]] = []
        specs: List[Tuple[float, str, int, int]] = []
        for a in lin_atoms:
            specs.append((float(a.expr.const), a.op, len(prods_spec),
                          len(a.expr.coeffs)))
            prods_spec.extend((c, float(v)) for c, v in a.expr.coeffs)
        n_prod = len(prods_spec)
        lin_cols = sorted({c for c, _ in prods_spec})
        n_host = len(host_atoms)

        def mul_body(*arrs):
            # every product is an output: XLA must emit the correctly
            # rounded multiply, and the accumulate program has no muls left
            return tuple(
                v * a.astype(jnp.float64)
                for (_, v), a in zip(prods_spec, arrs)
            )

        def mask_body(*args):
            prods, hosts = args[:n_prod], args[n_prod:]
            n = prods[0].shape[0]
            lin_iter = iter(specs)
            host_iter = iter(range(n_host))

            def ev(p: Pred):
                if p.kind == "true":
                    return jnp.ones(n, dtype=bool)
                if p.kind == "false":
                    return jnp.zeros(n, dtype=bool)
                if p.kind == "not":
                    return ~ev(p.children[0])
                if p.kind == "and":
                    m = jnp.ones(n, dtype=bool)
                    for c in p.children:
                        m = m & ev(c)
                    return m
                if p.kind == "or":
                    m = jnp.zeros(n, dtype=bool)
                    for c in p.children:
                        m = m | ev(c)
                    return m
                a = p.atom
                if isinstance(a, LinCmp) and a.expr.coeffs:
                    const, cmp_op, start, cnt = next(lin_iter)
                    out = jnp.full(n, const, dtype=jnp.float64)
                    for j in range(start, start + cnt):
                        out = out + prods[j]
                    if cmp_op == "<=":
                        return out <= 1e-12
                    if cmp_op == "<":
                        return out < -1e-12
                    if cmp_op == "==":
                        return jnp.abs(out) <= 1e-12
                    return jnp.abs(out) > 1e-12
                return hosts[next(host_iter)]

            return ev(pred)

        return _PredPlan(
            tuple(prods_spec), tuple(host_atoms), lin_cols,
            build_elementwise(mul_body), build_elementwise(mask_body),
            mul_body, mask_body,
        )

    def _eval_pred_plan(self, plan: _PredPlan, t: Table) -> np.ndarray:
        hosts = [eval_pred(Pred.of(a), t) for a in plan.host_atoms]
        with _x64():
            prods = plan.mul(*[t.cols[c] for c, _ in plan.prods_spec])
            out = plan.mask(*prods, *hosts)
        return np.asarray(out)

    def _filter(self, op: D.Operator, inputs: List[Table]) -> Table:
        plan = self._pred_plan(op.get("pred"))
        self._dispatched()
        return inputs[0].mask(self._eval_pred_plan(plan, inputs[0]))

    def pred_mask(self, pred, t: Table):
        """Delta-kernel mask: serve the vectorized two-program predicate
        kernel when it lowers for this table, else the reference bands —
        either way bit-identical to ``eval_pred`` (probed at compile)."""
        plan = self._pred_plan(pred)
        if plan is not None and _numeric(t, plan.lin_cols):
            self._dispatched()
            return self._eval_pred_plan(plan, t)
        return eval_pred(pred, t)

    def _proj_plan(self, cols):
        if not self.values_exact():
            return None
        key = repr(cols)
        plan = self._proj_plans.get(key)
        if plan is None:
            plan = self._compile_proj(cols) or _NO_PLAN
            self._proj_plans[key] = plan
        return None if plan is _NO_PLAN else plan

    def _compile_proj(self, cols) -> Optional[_ProjPlan]:
        _, jnp = _modules()
        from repro.engine.plane.kernels import build_elementwise

        prods_spec: List[Tuple[str, float]] = []
        items: List[Tuple[str, str, object]] = []
        lin_specs: List[Tuple[float, int, int]] = []
        for name, expr in cols:
            if isinstance(expr, str):
                items.append((name, "col", expr))
            else:
                lin_specs.append((float(expr.const), len(prods_spec),
                                  len(expr.coeffs)))
                prods_spec.extend((c, float(v)) for c, v in expr.coeffs)
                items.append((name, "lin", lin_specs[-1]))
        if not prods_spec:
            return None  # pure renames / constant exprs: reference is exact
        lin_cols = sorted({c for c, _ in prods_spec})

        def mul_body(*arrs):
            return tuple(
                v * a.astype(jnp.float64)
                for (_, v), a in zip(prods_spec, arrs)
            )

        def val_body(*prods):
            n = prods[0].shape[0]
            outs = []
            for const, start, cnt in lin_specs:
                out = jnp.full(n, const, dtype=jnp.float64)
                for j in range(start, start + cnt):
                    out = out + prods[j]
                outs.append(out)
            return tuple(outs)

        return _ProjPlan(
            tuple(prods_spec), tuple(items), lin_cols,
            build_elementwise(mul_body), build_elementwise(val_body),
            mul_body, val_body,
        )

    def _eval_proj_plan(self, plan: _ProjPlan, src: Table) -> Table:
        with _x64():
            prods = plan.mul(*[src.cols[c] for c, _ in plan.prods_spec])
            vals = plan.val(*prods)
        vals = vals if isinstance(vals, (tuple, list)) else (vals,)
        vals = [np.asarray(v) for v in vals]
        out_cols: Dict[str, np.ndarray] = {}
        order: List[str] = []
        vi = iter(vals)
        for name, kind, payload in plan.items:
            out_cols[name] = src.cols[payload] if kind == "col" else next(vi)
            order.append(name)
        return Table(out_cols, order)

    def _project(self, op: D.Operator, inputs: List[Table]) -> Table:
        plan = self._proj_plan(op.get("cols"))
        self._dispatched()
        return self._eval_proj_plan(plan, inputs[0])

    # -- JOIN: device probe over unique-compressed keys -----------------------
    def _probe(self):
        if self._join_probe is None:
            jax, _ = _modules()
            self._join_probe = jax.jit(_join_probe_body)
        return self._join_probe

    def _join(self, op: D.Operator, inputs: List[Table]) -> Table:
        left, right = inputs
        on = op.get("on")
        how = op.get("how", "inner")
        ren = {c: f"r_{c}" for c in right.order if c in left.order}
        r = right.rename(ren)
        r_on = [ren.get(rc, rc) for _, rc in on]
        l_on = [lc for lc, _ in on]
        nl, nr = len(left), len(r)

        # joint factorization: left and right key columns share one code
        # space per key position (dict-key equality incl. rounded collapse;
        # NaN keys get fresh codes so they never match — like the reference)
        with obs.span("veer.plane.join.codes", nl=nl, nr=nr,
                      keys=len(on)) as sp:
            code_cols = []
            n_sorted = 0
            for lc, rc in zip(l_on, r_on):
                both = np.concatenate(
                    [np.asarray(left.cols[lc]), np.asarray(r.cols[rc])]
                )
                codes, sorted_ = factorize(both, nan_distinct=True)
                code_cols.append(codes)
                n_sorted += sorted_
            joint = combine_codes(code_cols)
            lk, rk = joint[:nl], joint[nl:]
            max_code = int(joint.max()) if joint.size else 0
            # sparse codes go to the device probe (see below)
            device = max_code > max(1 << 22, 4 * (nl + nr))
            sp.set_metadata(device=int(device), sorted=n_sorted)

        # probe: per-left-row windows [lo[i], hi[i]) into ``order`` — the
        # right indices stably sorted by key, so each window lists a key's
        # matches in ascending right index.  Two equivalent probes:
        #
        #   * dense codes (range comparable to the table sizes, the common
        #     case since per-column codes come compressed): a bincount +
        #     exclusive-cumsum lookup table — O(1) gathers per left row, no
        #     per-query binary search;
        #   * sparse codes: the jitted blocked search (``_join_probe_body``)
        #     of the left keys into the sorted right keys: a compare-and-count
        #     against the fence key of each block of B right keys, then the
        #     same count over one gathered block per key.  The stable argsort
        #     stays on the host: XLA:TPU takes minutes to compile a sort at
        #     these sizes, and the probe seconds.  Both operands are
        #     bucket-padded by a sentinel above every possible code (codes
        #     stay < 2**61; see combine_codes) so jit compiles once per
        #     power-of-two bucket, not once per row count; B and the chunk
        #     of left keys follow from the buckets.  Sentinels sit at the
        #     tail of the sorted keys and no real key's window can reach them.
        with obs.span("veer.plane.join.argsort", nl=nl, nr=nr,
                      device=int(device)):
            order = np.argsort(rk, kind="stable")
        if not device:
            counts_all = np.bincount(rk, minlength=max_code + 1)
            ends_all = np.cumsum(counts_all)
            lo = (ends_all - counts_all)[lk]
            hi = ends_all[lk]
        else:
            _, jnp = _modules()
            from repro.engine.plane.kernels import pow2_bucket

            sentinel = np.int64(1) << 62
            lk_p = np.full(pow2_bucket(nl), sentinel, dtype=np.int64)
            lk_p[:nl] = lk
            sr_p = np.full(pow2_bucket(nr), sentinel, dtype=np.int64)
            sr_p[:nr] = rk[order]
            self._dispatched()
            # dispatch, the wait behind other threads' programs, the
            # program itself and both copies back
            block, _, _ = probe_layout(len(lk_p), len(sr_p))
            with obs.span("veer.plane.join.probe", nl=nl, nr=nr,
                          bucket_l=len(lk_p), bucket_r=len(sr_p), block=block):
                with _x64():
                    lo, hi = self._probe()(jnp.asarray(lk_p), jnp.asarray(sr_p))
                lo = np.asarray(lo)[:nl]
                hi = np.asarray(hi)[:nl]

        # expand the probe windows host-side, replicating the reference
        # output order exactly: left rows in order, each row's matches in
        # ascending right index (the stable argsort guarantees the window
        # order[lo[i]:hi[i]] is ascending), unmatched lefts appended after
        with obs.span("veer.plane.join.expand", nl=nl, nr=nr,
                      device=int(device)):
            counts = hi - lo
            li = np.repeat(np.arange(nl, dtype=np.int64), counts)
            starts_rep = np.repeat(lo, counts)
            csum = np.cumsum(counts)
            offs = np.arange(int(counts.sum()), dtype=np.int64) - np.repeat(
                csum - counts, counts
            )
            ri = order[starts_rep + offs]
            if how == "left_outer":
                unmatched = np.flatnonzero(counts == 0)
            else:
                unmatched = np.array([], dtype=np.int64)

            lt = left.take(np.concatenate([li, unmatched]).astype(int))
            out_cols = {c: lt.cols[c] for c in left.order}
            n_un = len(unmatched)
            for c in r.order:
                matched_vals = r.cols[c][ri] if len(ri) else r.cols[c][:0]
                if n_un:
                    if matched_vals.dtype == object:
                        pad = np.array([None] * n_un, dtype=object)
                    else:
                        # same canonical padding rule as the reference plane:
                        # np.nan pad, int columns upcast to float64
                        pad = np.full(n_un, np.nan)
                    matched_vals = np.concatenate([matched_vals, pad])
                out_cols[c] = matched_vals
        return Table(out_cols, left.order + r.order)

    # -- AGGREGATE: segment reduction over group codes ------------------------
    def _aggregate(self, op: D.Operator, inputs: List[Table]) -> Table:
        from repro.engine.canon import run_bounds
        from repro.engine.ops_impl import _col

        src = inputs[0]
        group_by = list(op.get("group_by", ()))
        aggs = op.get("aggs")
        n = len(src)

        cols: Dict[str, List] = {c: [] for c in group_by}
        for _, _, out in aggs:
            cols[out] = []

        if n:
            if group_by:
                codes = combine_codes(
                    [
                        column_codes(src.cols[c], nan_distinct=True)
                        for c in group_by
                    ]
                )
            else:
                codes = np.zeros(n, dtype=np.int64)
            order = np.argsort(codes, kind="stable")
            _, starts, ends = run_bounds(codes[order])
            # stable sort => each segment lists its group's rows in original
            # order, so order[starts] are the first-occurrence rows
            first_idx = order[starts]
            keys = [
                tuple(keyval(src.cols[c][int(fi)]) for c in group_by)
                for fi in first_idx
            ]
            # reference ordering: groups enumerated in first-occurrence
            # (dict-insertion) order, then stably sorted by repr(key) —
            # repr ties (NaN keys) keep insertion order
            occ = np.argsort(first_idx, kind="stable")
            gorder = sorted(occ.tolist(), key=lambda g: repr(keys[g]))
            for g in gorder:
                key = keys[g]
                rows = order[starts[g] : ends[g] + 1]
                for j, c in enumerate(group_by):
                    cols[c].append(key[j])
                for fn, c, out in aggs:
                    # contiguous float64 copy => identical pairwise
                    # summation to the reference's per-group reduction
                    vals = (
                        src.cols[c][rows].astype(np.float64)
                        if c != "*"
                        else None
                    )
                    if fn == "count":
                        cols[out].append(float(len(rows)))
                    elif fn == "sum":
                        cols[out].append(float(vals.sum()))
                    elif fn == "min":
                        cols[out].append(float(vals.min()))
                    elif fn == "max":
                        cols[out].append(float(vals.max()))
                    elif fn == "avg":
                        cols[out].append(float(vals.mean()))
                    else:  # pragma: no cover - guarded by lowers()
                        raise ValueError(f"agg fn {fn}")

        out_order = group_by + [out for _, _, out in aggs]
        return Table({c: _col(cols[c]) for c in out_order}, out_order)

    def _distinct(self, op: D.Operator, inputs: List[Table]) -> Table:
        src = inputs[0]
        n = len(src)
        if n == 0:
            return src.take(np.array([], dtype=int))
        codes = combine_codes(
            [column_codes(src.cols[c], nan_distinct=False) for c in src.order]
        )
        _, first = np.unique(codes, return_index=True)
        return src.take(np.sort(first))

    def _sort(self, op: D.Operator, inputs: List[Table]) -> Table:
        src = inputs[0]
        keys = list(op.get("keys"))
        # all-ascending numeric: one lexsort == the iterated stable argsort
        # (the stable lexicographic permutation is unique); primary key last
        idx = np.lexsort(tuple(src.cols[c] for c, _ in reversed(keys)))
        return src.take(idx)

    def _unnest(self, op: D.Operator, inputs: List[Table]) -> Table:
        src = inputs[0]
        col, out = op.get("col"), op.get("out")
        vals = src.cols[col]
        base = src.take(np.arange(len(src)))
        return base.with_col(
            out, vals.astype(np.float64) if len(vals) else np.array([])
        )

    def _dict_matcher(self, op: D.Operator, inputs: List[Table]) -> Table:
        src = inputs[0]
        col, out = op.get("col"), op.get("out")
        entries = set(op.get("entries"))
        arr = src.cols[col]
        if len(arr) == 0:
            return src.with_col(out, np.array([]))
        uniq, inv = np.unique(arr, return_inverse=True)
        hit = np.array([1.0 if v in entries else 0.0 for v in uniq])
        return src.with_col(out, hit[inv.reshape(-1)])

    def _classifier(self, op: D.Operator, inputs: List[Table]) -> Table:
        src = inputs[0]
        col, out = op.get("col"), op.get("out")
        model = op.get("model", "default")
        k = int(op.get("classes", 3))
        salt = f"{op.op_type}:{model}"
        arr = src.cols[col]
        if len(arr) == 0:
            h = np.empty(0, dtype=np.int64)
        else:
            import zlib

            uniq, inv = np.unique(arr, return_inverse=True)
            hu = np.empty(len(uniq), dtype=np.int64)
            for i, v in enumerate(uniq):
                hu[i] = zlib.crc32((salt + ":" + repr(v)).encode()) & 0x7FFFFFFF
            h = hu[inv.reshape(-1)]
        return src.with_col(out, (h % k).astype(np.float64))

    # -- reporting ------------------------------------------------------------
    def kernel_specs(self, n: int) -> List[Tuple[str, object, list]]:
        """The plane's representative jitted kernels as ``(name, body,
        avals)`` at ``n`` rows; trace or compile them under ``jax.enable_x64``."""
        from fractions import Fraction

        from repro.core.predicates import LinExpr

        jax, _ = _modules()
        e1 = LinExpr.make({"a": Fraction(5, 2), "b": -1}, 1)
        e2 = LinExpr.make({"c": Fraction(1, 3)}, Fraction(-1, 2))
        pred = Pred.and_(Pred.of(LinCmp(e1, "<=")), Pred.of(LinCmp(e2, "<")))
        pplan = self._compile_pred(pred)
        jplan = self._compile_proj((("x", e1), ("y", e2)))
        f64 = jax.ShapeDtypeStruct((n,), np.float64)
        i64 = jax.ShapeDtypeStruct((n,), np.int64)
        return [
            ("filter_mul", pplan.mul_body, [f64] * len(pplan.prods_spec)),
            ("filter_mask", pplan.mask_body, [f64] * len(pplan.prods_spec)),
            ("project_sum", jplan.val_body, [f64] * len(jplan.prods_spec)),
            ("join_probe", _join_probe_body, [i64, i64]),
        ]

    def roofline_report(self, n: int = 1_000_000) -> List[Dict]:
        """Roofline terms of ``kernel_specs(n)`` on the default device
        (consumed by ``benchmarks/plane_bench.py``); a device without
        published peaks raises ``UnknownDevice``.  Kernels are compiled
        from shapes — no device allocation."""
        from repro.engine.plane.roofline import kernel_roofline

        jax, _ = _modules()
        kind = jax.devices()[0].device_kind
        report: List[Dict] = []
        with _x64():
            for name, fn, args in self.kernel_specs(n):
                r = kernel_roofline(fn, *args, device_kind=kind)
                report.append(
                    {
                        "kernel": name,
                        "rows": n,
                        "device_kind": kind,
                        "flops": r.flops,
                        "hbm_bytes": r.hbm_bytes,
                        "t_compute_s": r.t_compute,
                        "t_memory_s": r.t_memory,
                        "bottleneck": r.bottleneck,
                        "bandwidth_bound": r.t_memory >= r.t_compute,
                    }
                )
        return report


#: elements of one chunk's ``(chunk, block)`` row gather in the join probe
#: (32 MB of int64; on a TPU v5e 2^20 and 2^24 were both slower)
_PROBE_CHUNK_ELEMS = 1 << 22


def probe_layout(n_l: int, n_r: int) -> Tuple[int, int, int]:
    """Static layout of the blocked join probe for ``n_l`` left keys into
    ``n_r`` sorted right keys: the block width ``B = 2^ceil(log2(n_r)/2)``,
    the block count ``M`` (the right keys padded up to ``M * B``) and the
    number of left keys probed per chunk, which keeps the chunk's
    ``(chunk, B)`` row gather at ``_PROBE_CHUNK_ELEMS`` elements."""
    block = 1 << (max(n_r - 1, 0).bit_length() + 1) // 2
    blocks = max(1, -(-n_r // block))
    chunk = max(1, min(n_l, _PROBE_CHUNK_ELEMS // block))
    return block, blocks, chunk


def _join_probe_body(lk, sr):
    """Sorted-probe join kernel: each left key's window ``[lo, hi)`` in the
    sorted right keys ``sr``, equal to ``np.searchsorted(sr, lk, "left")``
    and ``(..., "right")``.

    A two-level blocked search (``probe_layout``): the right keys, padded
    with the largest int64, form ``M`` rows of ``B``, and each row's first
    key is a fence.  A key ``x`` counts ``b`` fences below it (``<`` for
    ``lo``, ``<=`` for ``hi``); every row before row ``b - 1`` lies wholly
    below ``x`` and every row from ``b`` on wholly above it, so the window
    bound is ``(b - 1) * B`` plus the same count over the one gathered row
    ``b - 1`` (row 0 when ``b`` is 0, where that count is 0).  ``lo`` and
    ``hi`` take their own fence counts, since a run of equal keys may span
    rows.  Left keys go in chunks (``lax.map``) to bound the gathered rows.
    Only int64 compares and counts, so the windows are exact and the
    host-side expansion reproduces the reference bytes."""
    import jax.numpy as jnp
    from jax import lax

    n_l, n_r = lk.shape[0], sr.shape[0]
    block, m, chunk = probe_layout(n_l, n_r)
    top = jnp.iinfo(jnp.int64).max
    rows = jnp.pad(sr, (0, m * block - n_r), constant_values=top).reshape(m, block)
    fences = rows[:, 0]
    n_chunks = -(-n_l // chunk)
    keys = jnp.pad(lk, (0, n_chunks * chunk - n_l)).reshape(n_chunks, chunk)

    def bound(x, below):
        b = jnp.sum(below(fences, x[:, None]), axis=1, dtype=jnp.int32)
        j = jnp.maximum(b - 1, 0)
        row = rows.at[j].get(mode="promise_in_bounds")
        return j * block + jnp.sum(below(row, x[:, None]), axis=1, dtype=jnp.int32)

    def probe(x):
        # a key equal to the padding counts the padding too; every real
        # right key lies at or below it, so the count caps at n_r
        return bound(x, jnp.less), jnp.minimum(bound(x, jnp.less_equal), n_r)

    lo, hi = lax.map(probe, keys)
    return lo.reshape(-1)[:n_l], hi.reshape(-1)[:n_l]


def use_compile_cache(root) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is configured here.  Otherwise the cache goes to
    ``<root>/.jax_cache``: one fixed path, because the path is part of
    what a later process must match to find the entries.  Called by entry
    points (``chip_smoke.py``, the benchmarks), never on import.
    """
    import os

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax, _ = _modules()
    path = os.path.join(os.fspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _first_mismatch(what: str, got: np.ndarray, want: np.ndarray) -> Optional[str]:
    """None if ``got`` is bit-identical to ``want``, else where it is not."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        return (f"{what}: kernel {got.dtype}{got.shape}, "
                f"reference {want.dtype}{want.shape}")
    if got.dtype.kind == "f":  # compare bits: -0.0 vs 0.0 and NaNs count
        got_b, want_b = got.view(f"u{got.itemsize}"), want.view(f"u{want.itemsize}")
        bad = np.flatnonzero(got_b != want_b)
    else:
        bad = np.flatnonzero(got != want)
    if not len(bad):
        return None
    i = int(bad[0])
    return (f"{what} row {i}: kernel {got[i]!r}, reference {want[i]!r} "
            f"({len(bad)} of {len(got)} rows differ)")


def _numeric(t: Table, cols) -> bool:
    return all(c in t.cols and t.cols[c].dtype != object for c in cols)


def _mixed_zero_signs(col: np.ndarray) -> bool:
    """True when a float column holds both -0.0 and +0.0 (their reprs
    differ but ``np.unique`` collapses them — the classifier hash must
    fall back to the per-row reference)."""
    if col.dtype.kind != "f":
        return False
    zeros = col == 0.0
    if not zeros.any():
        return False
    sb = np.signbit(col[zeros])
    return bool(sb.any() and not sb.all())
