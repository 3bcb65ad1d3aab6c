"""What one version pair's EV work has already decided.

Algorithm 2 asks the EVs about many windows of one pair, and the windows
overlap: they share operators, and a window with several sinks normalizes
the cone those sinks share once per sink.  So the same normal forms
(``relational.normalize``), the same comparisons of them and the same
conjunctions (``solver.satisfiable``) come up again and again: on TPC-DS
Q50's five-sink pairs each distinct conjunction was decided about 45
times.  ``PairMemo`` keeps the answers for as long as one pair is
verified:

* ``sat``: a conjunction, as the frozenset of its atoms (their order and
  repeats do not change satisfiability), to whether it is satisfiable;
  ``len(sat)`` is the number of satisfiability problems the pair decided;
* ``blocks``: an operator's structure (the operator object and the keys
  of its inputs, a source by its id and schema) to its normal form;
* ``equivalent``: two normal forms, by identity, to whether they are
  bag-equivalent (``relational.blocks_equivalent``): a sink's cones are
  shared by many windows, and so are their normal forms.

All are pure functions of their keys, so a kept answer is the answer a
fresh computation gives: verdicts and certificates do not depend on
whether a memo was active.  ``scope(memo)`` makes ``memo`` the calling
thread's for a block; outside any scope the decision procedures compute
afresh and remember nothing.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
from typing import Callable, Dict, FrozenSet, Iterator, Optional, Tuple

from repro.core.dag import SOURCE

_local = threading.local()


class PairMemo:
    __slots__ = ("sat", "blocks", "equivalent", "_keys", "_numbers", "_pinned")

    def __init__(self) -> None:
        self.sat: Dict[FrozenSet, bool] = {}
        self.blocks: Dict[Tuple[int, bool], object] = {}
        self.equivalent: Dict[Tuple[int, int], Tuple] = {}
        self._keys: Dict[Tuple, int] = {}
        self._numbers = itertools.count()
        # operators whose id() is part of a key: pinned so no id is reused
        # by another object while the memo lives
        self._pinned: Dict[int, object] = {}

    def node_key(self, op, inputs: Tuple[int, ...]) -> int:
        """A small int naming ``op`` over inputs with keys ``inputs``:
        equal keys, equal sub-queries.  Sources are named by id and schema,
        all their normal form holds; other operators by identity, since
        windows share their version's operator objects and an operator is
        immutable."""
        if op.op_type == SOURCE:
            raw: Tuple = ("src", op.id, tuple(op.get("schema") or ()))
        else:
            self._pinned[id(op)] = op
            raw = (id(op), inputs)
        key = self._keys.get(raw)
        if key is None:
            # a fresh number per call: two threads interning two structures
            # at once never share one (``next`` on a count is atomic)
            key = self._keys.setdefault(raw, next(self._numbers))
        return key


def active() -> Optional[PairMemo]:
    """The calling thread's memo, or ``None``."""
    return getattr(_local, "memo", None)


@contextlib.contextmanager
def scope(memo: Optional[PairMemo]) -> Iterator[None]:
    outer = active()
    _local.memo = memo
    try:
        yield
    finally:
        _local.memo = outer


def run_in(memo: Optional[PairMemo], fn: Callable, *args):
    """``fn(*args)`` with ``memo`` active: how a worker thread joins the
    pair it works for."""
    with scope(memo):
        return fn(*args)
