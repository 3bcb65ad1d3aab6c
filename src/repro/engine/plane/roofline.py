"""Roofline terms of the data plane's jitted kernels on one chip.

  compute term = HLO_FLOPs / peak_FLOP/s
  memory term  = HLO_bytes / HBM_bw

``compiled.cost_analysis()`` counts while-loop bodies ONCE, so a kernel
that loops (the join probe's chunk loop) under-reports its work.  We
therefore parse the optimized HLO ourselves: build the computation graph,
read each while op's ``known_trip_count`` from its backend_config, and
accumulate

  * flops        — every ``dot`` op: 2 · |result| · |contraction dims|,
  * HBM traffic  — modeled for a WELL-FUSED TPU program: we count operand +
                   result bytes of data-movement ops (dot, conv, gather,
                   scatter, copy, transpose, dynamic-(update-)slice at slice
                   granularity, iota-free broadcasts excluded) — NOT every
                   fusion boundary, whose intermediates a TPU keeps in VMEM.

each × the product of enclosing loop trip counts.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float   # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip


#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
#: TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
#: 819 GB/s HBM.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9),
}


class UnknownDevice(ValueError):
    """No published peaks for this ``device_kind``: no time or share can
    be computed for it."""


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1, "token": 0,
}

# ops that MOVE data in a well-fused program (everything else is assumed
# fused into a neighbor's VMEM pipeline)
_TRAFFIC_OPS = {
    "dot", "convolution", "gather", "scatter", "copy", "transpose",
    "concatenate", "pad", "reduce", "sort",
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in re.finditer(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str):
        dtype, dims = m.group(1), m.group(2)
        b = _DTYPE_BYTES.get(dtype)
        if b is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * b
    return total


def _shape_dims(shape_str: str) -> List[int]:
    m = re.match(r"[a-z0-9]+\[([0-9,]*)\]", shape_str)
    if not m:
        return []
    return [int(d) for d in m.group(1).split(",") if d]


_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$")
_SHAPE_RE = re.compile(r"([a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)")
_KIND_RE = re.compile(r"([\w\-]+)\(")


def _parse_op_line(line: str):
    """'%name = <shape|tuple> kind(args...' -> (name, shape, kind, args)."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if not s.startswith("%"):
        return None
    eq = s.find(" = ")
    if eq < 0:
        return None
    name = s[1:eq]
    rest = s[eq + 3 :]
    if rest.startswith("("):  # tuple type — balanced paren scan
        depth = 0
        end = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        shape = rest[: end + 1]
        rest2 = rest[end + 1 :].lstrip()
    else:
        m = _SHAPE_RE.match(rest)
        if not m:
            return None
        shape = m.group(1)
        rest2 = rest[m.end() :].lstrip()
    m2 = _KIND_RE.match(rest2)
    if not m2:
        return None
    return name, shape, m2.group(1), rest2[m2.end() :]


@dataclasses.dataclass
class HLOAnalysis:
    flops: float
    traffic_bytes: float


def analyze_hlo(hlo_text: str) -> HLOAnalysis:
    lines = hlo_text.splitlines()
    comps: Dict[str, List[Tuple[str, str, str, str]]] = {}  # name -> ops
    entry: Optional[str] = None
    cur: Optional[str] = None
    for line in lines:
        hdr = _COMP_HDR.match(line)
        if hdr:
            cur = hdr.group(1)
            comps[cur] = []
            if line.startswith("ENTRY"):
                entry = cur
            continue
        if cur is None or line.strip() in ("}", ""):
            continue
        parsed = _parse_op_line(line)
        if parsed:
            comps[cur].append(parsed)

    if entry is None:
        # fall back: last computation is usually the entry
        entry = list(comps)[-1] if comps else ""

    # per-computation symbol tables (op name -> result shape string)
    symtab: Dict[str, Dict[str, str]] = {
        c: {name: shape for name, shape, _, _ in ops} for c, ops in comps.items()
    }

    # while edges: (computation, body, cond, trip)
    while_edges: Dict[str, List[Tuple[str, str, int]]] = {c: [] for c in comps}
    for c, ops in comps.items():
        for name, shape, kind, rest in ops:
            if kind != "while":
                continue
            bm = re.search(r"body=%?([\w\.\-]+)", rest)
            cm = re.search(r"condition=%?([\w\.\-]+)", rest)
            tm = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', rest)
            trip = int(tm.group(1)) if tm else 1
            if bm:
                while_edges[c].append((bm.group(1), cm.group(1) if cm else "", trip))

    # multipliers: walk entry through while edges (+conditional branches ×1)
    mult: Dict[str, int] = {}

    def walk(c: str, m: int, depth: int = 0):
        if depth > 32 or c not in comps:
            return
        if mult.get(c, 0) >= m:
            return
        mult[c] = m
        for body, cond, trip in while_edges.get(c, []):
            walk(body, m * trip, depth + 1)
            walk(cond, m * trip, depth + 1)
        for name, shape, kind, rest in comps[c]:
            if kind == "conditional":
                for callee in re.findall(r"(?:branch_computations=\{|true_computation=|false_computation=)%?([\w\.\-,% ]+)", rest):
                    for cc in re.split(r"[,\s%]+", callee):
                        if cc:
                            walk(cc, m, depth + 1)

    walk(entry, 1)

    flops = 0.0
    traffic = 0.0

    # FLOPs: dots wherever they appear; fusion computations inherit the
    # multiplier of the computation that calls them.
    fusion_mult: Dict[str, int] = {}
    for c, ops in comps.items():
        base = mult.get(c)
        if base is None:
            continue
        for name, shape, kind, rest in ops:
            for callee in re.findall(r"calls=%?([\w\.\-]+)", rest):
                fusion_mult[callee] = max(fusion_mult.get(callee, 0), base)
            for callee in re.findall(r"to_apply=%?([\w\.\-]+)", rest):
                fusion_mult[callee] = max(fusion_mult.get(callee, 0), base)

    def comp_mult(c: str) -> int:
        return mult.get(c, fusion_mult.get(c, 0))

    for c, ops in comps.items():
        m = comp_mult(c)
        if not m:
            continue
        st = symtab[c]
        in_real = c in mult  # not a fusion body
        for name, shape, kind, rest in ops:
            operand_str = rest.split(")")[0]
            opnames = re.findall(r"%([\w\.\-]+)", operand_str)
            if kind in ("dot", "convolution"):
                dims = _shape_dims(shape)
                out_elems = 1
                for d in dims:
                    out_elems *= d
                contr = 1
                lm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rest)
                if lm and opnames:
                    lhs_shape = st.get(opnames[0], "")
                    ldims = _shape_dims(lhs_shape)
                    for idx in lm.group(1).split(","):
                        if idx and int(idx) < len(ldims):
                            contr *= ldims[int(idx)]
                flops += 2.0 * out_elems * contr * m
            # fused-TPU traffic model: only data-movement ops count.
            # dot/conv/gather/scatter/slice-updates count wherever they
            # appear; layout ops (copy/transpose/...) only at top level —
            # inside a fusion they are VMEM-resident.
            if kind == "dynamic-update-slice":
                upd = st.get(opnames[1], "") if len(opnames) > 1 else ""
                traffic += 2 * _shape_bytes(upd) * m
            elif kind == "dynamic-slice":
                traffic += 2 * _shape_bytes(shape) * m
            elif kind in ("dot", "convolution", "gather", "scatter"):
                ob = sum(_shape_bytes(st.get(o, "")) for o in opnames)
                traffic += (_shape_bytes(shape) + ob) * m
            elif in_real and kind in _TRAFFIC_OPS:
                ob = sum(_shape_bytes(st.get(o, "")) for o in opnames)
                traffic += (_shape_bytes(shape) + ob) * m

    return HLOAnalysis(flops=flops, traffic_bytes=traffic)


@dataclasses.dataclass
class Roofline:
    flops: float               # HLO flops (trip-count corrected)
    hbm_bytes: float           # HLO bytes (trip-count corrected)
    device_kind: str           # key into PEAKS; unknown kinds raise

    @property
    def t_compute(self) -> float:
        return self.flops / peaks(self.device_kind).flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / peaks(self.device_kind).hbm_bw

    @property
    def bottleneck(self) -> str:
        return "compute" if self.t_compute >= self.t_memory else "memory"


def analyze_jitted(fn, *args) -> HLOAnalysis:
    """Trip-count-corrected HLO analysis of ``jax.jit(fn)`` on ``args``.

    jax is imported lazily — this module stays importable on hosts without
    jax.
    """
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    return analyze_hlo(compiled.as_text())


def kernel_roofline(fn, *args, device_kind: str) -> Roofline:
    """Single-chip roofline for one jitted kernel.

    The fused-program traffic model (module docstring) counts only interior
    data-movement ops, which reports **zero** bytes for a pure elementwise
    kernel — but a standalone kernel must still stream its operands and
    results through HBM, so entry I/O bytes are applied as a floor.
    """
    import math

    import jax

    an = analyze_jitted(fn, *args)
    leaves = list(args) + list(jax.tree_util.tree_leaves(jax.eval_shape(fn, *args)))
    io_bytes = sum(
        math.prod(x.shape) * x.dtype.itemsize for x in leaves
    )
    return Roofline(
        flops=an.flops,
        hbm_bytes=max(an.traffic_bytes, float(io_bytes)),
        device_kind=device_kind,
    )
