"""EV protocol (paper §2.3, §4.2).

An EV takes a pair of queries and returns True (equivalent), False
(inequivalent) or None (Unknown).  Each EV publishes *restrictions* — a
validator deciding whether a window/query pair is inside the fragment the EV
can decide (Def 4.2/4.3) — plus two capability bits the verifier relies on:

  * ``restriction_monotonic`` (Def 5.9): expanding an invalid window can
    never make it valid.  Spes-like EVs have it; Equitas-like do not (R5/R6
    counting restrictions), which changes how Algorithm 2 marks maximality.
  * ``can_prove_inequivalence``: only such EVs may drive a False verdict
    (paper §4.4 note about COSETTE).
"""

from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field, replace
from typing import Dict, FrozenSet, Iterator, List, Optional, Sequence, Tuple

from repro import obs
from repro.core.dag import BAG, ORDERED, SET, SOURCE, DataflowDAG


@dataclass(frozen=True)
class QueryPair:
    """Two stand-alone sub-DAGs with aligned symbolic sources and sinks.

    Source operators carry the *same id* on both sides (the window boundary
    correspondence), so "for every instance of source operators" (Def 3.4)
    means binding equal tables to equal ids.
    """

    P: DataflowDAG
    Q: DataflowDAG
    sink_pairs: Tuple[Tuple[str, str], ...]
    semantics: str = BAG
    at_version_sink: bool = False  # window sinks are the versions' sinks

    def key(self) -> Tuple:
        return (
            self.P.signature(),
            self.Q.signature(),
            self.sink_pairs,
            self.semantics,
            self.at_version_sink,
        )

    def materialized(self) -> "QueryPair":
        """This pair with ``P`` and ``Q`` as ``DataflowDAG``s: itself,
        unless its sides are views of a window over the version DAGs
        (``repro.core.window.WindowDAG``), whose sub-DAGs are then built."""
        if isinstance(self.P, DataflowDAG) and isinstance(self.Q, DataflowDAG):
            return self
        return replace(self, P=self.P.materialize(), Q=self.Q.materialize())

    def fingerprint(self) -> str:
        """Content-addressed canonical key, invariant under operator renames.

        ``key()`` above is id-sensitive: the same rewrite applied to a renamed
        copy of a workflow (or re-encountered in a later version pair, where
        ids drifted) produces a different key.  ``fingerprint()`` erases ids —
        operators are named by their position in a canonical traversal, and
        source operators by a token assigned on first appearance that is
        *shared across the two sides* (same source id on both sides ⇒ same
        token, which is exactly the pairing EV verdicts depend on).  Two
        query pairs with equal fingerprints are isomorphic as pairs, so every
        (deterministic, id-invariant) EV returns the same verdict on both —
        the soundness condition for the cross-version verdict cache.

        Canonicalization: each sink pair serializes both sub-DAG cones in
        consumer-port order, with internal sharing captured by back-references
        (``("ref", i)``); sink pairs are ordered by an id-free local
        serialization first, so the global source-token assignment does not
        depend on the incoming ``sink_pairs`` order.
        """
        cached = self.__dict__.get("_fingerprint")
        if cached is not None:
            return cached
        pairs = list(self.sink_pairs)
        if len(pairs) > 1:
            # each pair's local serialization is produced only as far as
            # the sort's comparisons need it
            local = {pq: _Items(self._local_items(*pq)) for pq in pairs}
            pairs.sort(key=lambda pq: local[pq])
        tokens: Dict[str, int] = {}
        ix_p: Dict[str, int] = {}
        ix_q: Dict[str, int] = {}
        stream: List[str] = []
        for ps, qs in pairs:
            stream.append(_SINK)
            stream.extend(_cone_items(self.P, ps, tokens, ix_p))
            stream.append(_SIDE)
            stream.extend(_cone_items(self.Q, qs, tokens, ix_q))
        blob = (f"({self.semantics!r}, {self.at_version_sink!r}, "
                f"{_repr_list(stream)})")
        digest = hashlib.sha256(blob.encode()).hexdigest()[:32]
        object.__setattr__(self, "_fingerprint", digest)  # frozen-safe memo
        return digest

    def _local_items(self, ps: str, qs: str) -> Iterator[str]:
        """One sink pair's id-free serialization on its own: fresh source
        tokens and operator indices."""
        tokens: Dict[str, int] = {}
        yield from _cone_items(self.P, ps, tokens, {})
        yield _SIDE
        yield from _cone_items(self.Q, qs, tokens, {})


# the stream's items, each written as ``repr`` writes its tuple: the stream
# is a list of them, and ``_repr_list`` joins them as ``repr`` of that list
_SIDE = repr(("side",))
_SINK = repr(("sink",))
_END = repr(("end",))


def _repr_list(items: List[str]) -> str:
    return "[" + ", ".join(items) + "]"


class _Items:
    """A sink pair's local serialization, drawn from its generator as far
    as comparisons need, ordered as ``_repr_list`` of the whole sequence
    would order: item by item (one item's repr is never a proper prefix
    of another's), and where one sequence is a prefix of the other the
    longer first (``", "`` sorts before ``"]"``)."""

    __slots__ = ("items", "rest")

    def __init__(self, items: Iterator[str]):
        self.items: List[str] = []
        self.rest = items

    def _at(self, i: int) -> Optional[str]:
        while len(self.items) <= i:
            nxt = next(self.rest, None)
            if nxt is None:
                return None
            self.items.append(nxt)
        return self.items[i]

    def __lt__(self, other: "_Items") -> bool:
        i = 0
        while True:
            a, b = self._at(i), other._at(i)
            if a is None or b is None:
                return a is not None and b is None
            if a != b:
                return a < b
            i += 1


def _cone_items(
    dag: DataflowDAG,
    root: str,
    source_tokens: Dict[str, int],
    node_ix: Dict[str, int],
) -> Iterator[str]:
    """An id-free serialization of the cone feeding ``root``, item by item.

    The stream is flat (balanced ``begin``/``end`` markers instead of nested
    tuples) and the traversal iterative, so arbitrarily deep pipelines neither
    overflow the interpreter stack nor break ``repr``.  Non-source operators
    are indexed in post-order of first completion; revisits (fan-out sharing)
    serialize as ``("ref", index)``.  Sources serialize as
    ``("src", token, signature)`` where the token dict is shared between the
    P and Q sides of a pair (ids coincide there by construction), making the
    cross-side source correspondence part of the canonical form.  Each item
    is the ``repr`` of that tuple, with the operator's signature rendered
    once per operator (``Operator.signature_repr``).
    """
    stack: List[Tuple[str, str]] = [("visit", root)]
    while stack:
        action, op_id = stack.pop()
        if action == "end":
            node_ix[op_id] = len(node_ix)
            yield _END
            continue
        op = dag.ops[op_id]
        if op.op_type == SOURCE:
            tok = source_tokens.setdefault(op_id, len(source_tokens))
            yield f"('src', {tok}, {op.signature_repr()})"
            continue
        if op_id in node_ix:
            yield f"('ref', {node_ix[op_id]})"
            continue
        yield f"('begin', {op.signature_repr()})"
        stack.append(("end", op_id))
        for l in reversed(dag.in_links.get(op_id, ())):
            stack.append(("visit", l.src))


@dataclass(frozen=True)
class Restriction:
    """One named EV restriction, e.g. Equitas R1..R6 (§4.2)."""

    name: str
    description: str


class BaseEV:
    name: str = "base"
    semantics: FrozenSet[str] = frozenset({SET, BAG, ORDERED})
    restriction_monotonic: bool = False
    can_prove_inequivalence: bool = False
    supported_op_types: FrozenSet[str] = frozenset()

    def restrictions(self) -> List[Restriction]:
        return []

    def validate(self, qp: QueryPair) -> bool:
        """True iff the pair satisfies this EV's restrictions (valid window,
        Def 4.3)."""
        raise NotImplementedError

    def failed_restrictions(self, qp: QueryPair) -> List[str]:
        """Names of violated restrictions (for Table-1-style reporting)."""
        return [] if self.validate(qp) else ["unspecified"]

    def check(self, qp: QueryPair) -> Optional[bool]:
        """Equivalence verdict; callers must have validated first."""
        raise NotImplementedError

    def __repr__(self) -> str:
        return f"EV({self.name})"


VERDICT_NAMES = {True: "eq", False: "neq", None: "unk"}


def timed_check(ev: BaseEV, qp: QueryPair) -> Tuple[Optional[bool], float]:
    """``ev.check(qp)`` and its wall seconds, inside a ``veer.ev.check``
    span: ``ev`` the EV's name, ``ops`` the window's operators on both
    sides (symbolic inputs included), ``verdict`` eq/neq/unk."""
    t0 = time.perf_counter()
    with obs.span("veer.ev.check", ev=ev.name, ops=len(qp.P.ops) + len(qp.Q.ops)) as sp:
        verdict = ev.check(qp)
        sp.set_metadata(verdict=VERDICT_NAMES[verdict])
    return verdict, time.perf_counter() - t0


class EVCallCounter:
    """Wraps an EV to count/check calls — the experiments report EV-call
    overhead separately (paper Table 6)."""

    def __init__(self, ev: BaseEV):
        self.ev = ev
        self.calls = 0
        self.validate_calls = 0
        self.time_in_check = 0.0

    def __getattr__(self, item):
        return getattr(self.ev, item)

    def validate(self, qp: QueryPair) -> bool:
        self.validate_calls += 1
        return self.ev.validate(qp)

    def check(self, qp: QueryPair) -> Optional[bool]:
        import time

        self.calls += 1
        t0 = time.perf_counter()
        try:
            return self.ev.check(qp)
        finally:
            self.time_in_check += time.perf_counter() - t0
