"""Vector Volcano operator API (paper §3.1).

Each operator pulls *batches* from its children via ``next_batch()`` and may
reposition sorted children via ``skip()`` — BARQ's distinguishing addition to
the vectorized pull model. ``reset()`` restarts iteration (used by the legacy
bind join and by tests). Operators expose per-operator runtime statistics so
the profiler can print Listing-1/3/5-style plans.
"""

from __future__ import annotations

import time
from typing import List, Optional, Tuple

from repro.core import batch as _batch
from repro.core import telemetry
from repro.core.batch import ColumnBatch


class OpStats:
    __slots__ = (
        "name",
        "detail",
        "results",
        "batches",
        "next_calls",
        "skip_calls",
        "reset_calls",
        "wall_time",
        "rows_scanned",
        "est_rows",
        "est_source",
        "node_fp",
        "extra",
    )

    def __init__(self, name: str, detail: str = "") -> None:
        self.name = name
        self.detail = detail
        self.results = 0  # output rows (active)
        self.batches = 0  # output batches
        self.next_calls = 0  # next() calls received
        self.skip_calls = 0  # skip() calls received
        self.reset_calls = 0
        self.wall_time = 0.0  # seconds spent inside this operator (self+children)
        self.rows_scanned = 0  # storage rows read (scans only; overfetch metric)
        # planner cardinality estimate for this operator's Phys node, or
        # None when lowering had no estimate (EXPLAIN ANALYZE input)
        self.est_rows: Optional[float] = None
        # where the estimate came from: "stats" (cost model) or "feedback"
        # (observed-cardinality override, DESIGN.md §14)
        self.est_source: str = "stats"
        # the Phys node's stable fingerprint (planner), or None for
        # programmatically built trees / adapters — the key the executor
        # records actual cardinalities under
        self.node_fp: Optional[str] = None
        # operator-specific counters (e.g. PathExpand frontier rounds /
        # dedup ratio); the profiler prints and aggregates them generically
        self.extra: dict = {}


class BatchOperator:
    """Base class: pull-based batch iteration with skip support."""

    # join operators set this: the rows they emit, and Σ of those rows'
    # weights, are charged to the request's trace (``count_join_rows``)
    emits_join_rows = False

    def __init__(self, name: str, detail: str = "") -> None:
        self.stats = OpStats(name, detail)

    # -- public API (wrapped for stats) --------------------------------------

    def next_batch(self) -> Optional[ColumnBatch]:
        self.stats.next_calls += 1
        san = _batch._SANITIZER
        if san is not None:
            # pool-sanitizer attribution scope (DESIGN.md §16): batches
            # acquired while this operator runs carry its name, so
            # leak / use-after-release reports name the allocating operator
            san.push_op(self.stats.name)
        t0 = time.perf_counter()
        try:
            b = self._next()
        finally:
            self.stats.wall_time += time.perf_counter() - t0
            if san is not None:
                san.pop_op()
        if b is not None:
            n = b.n_active
            self.stats.batches += 1
            self.stats.results += n
            if self.emits_join_rows and n:
                telemetry.count_join_rows(b, n)
        return b

    def skip(self, var: int, target: int) -> None:
        """Reposition so subsequent batches only contain rows with
        column ``var`` >= ``target``. Only valid if ``sorted_by() == var``."""
        self.stats.skip_calls += 1
        t0 = time.perf_counter()
        self._skip(var, target)
        self.stats.wall_time += time.perf_counter() - t0

    def reset(self) -> None:
        self.stats.reset_calls += 1
        self._reset()

    # -- metadata -------------------------------------------------------------

    def var_ids(self) -> Tuple[int, ...]:
        raise NotImplementedError

    def sorted_by(self) -> Optional[int]:
        return None

    def supports_skip(self) -> bool:
        return self.sorted_by() is not None

    def can_skip(self, var: Optional[int]) -> bool:
        """True iff skip(var, ...) is valid on this operator — queryable so
        callers (SIP range narrowing, join galloping) choose mask-mode
        fallbacks instead of relying on ValueError control flow."""
        return var is not None and self.sorted_by() == var

    def children(self) -> List["BatchOperator"]:
        return []

    # -- resource teardown -----------------------------------------------------

    def close(self) -> None:
        """Release external resources (spill files, mapped buffers) for this
        operator and its whole subtree. Idempotent; stats survive — the
        executor calls this in a ``finally`` so EXPLAIN ANALYZE still works
        after a mid-query exception (the ISSUE-9 spill-leak fix)."""
        close_tree(self)

    def _close(self) -> None:
        """Per-operator teardown hook — release disk/buffers only."""

    # -- implementation hooks ---------------------------------------------------

    def _next(self) -> Optional[ColumnBatch]:
        raise NotImplementedError

    def _skip(self, var: int, target: int) -> None:
        raise NotImplementedError(f"{self.stats.name} does not support skip()")

    def _reset(self) -> None:
        raise NotImplementedError

    # -- convenience --------------------------------------------------------------

    def drain(self) -> List[ColumnBatch]:
        out = []
        while True:
            b = self.next_batch()
            if b is None:
                return out
            if b.n_active:
                out.append(b)


class CloseError(RuntimeError):
    """One or more ``_close`` hooks raised during tree teardown. The walk
    still visited every operator first; ``errors`` carries each failure as
    (operator name, exception)."""

    def __init__(self, errors) -> None:
        self.errors = list(errors)
        detail = "; ".join(
            f"{name}: {type(e).__name__}: {e}" for name, e in self.errors
        )
        super().__init__(
            f"{len(self.errors)} operator close() failure(s): {detail}"
        )


def close_tree(op) -> None:
    """Walk an operator tree (batch or row; duck-typed on ``children``) and
    invoke every ``_close`` hook. An exception from one hook doesn't stop
    the walk — a failed unlink must not leak the rest of the tree's spill
    files — but it is not swallowed either: after every operator has been
    visited, the collected failures re-raise as one ``CloseError``."""
    stack = [op]
    errors = []
    while stack:
        o = stack.pop()
        cl = getattr(o, "_close", None)
        if cl is not None:
            try:
                cl()
            except Exception as e:  # keep closing siblings first
                errors.append((getattr(o, "stats", o).name
                               if hasattr(o, "stats") else type(o).__name__, e))
        ch = getattr(o, "children", None)
        if ch is not None:
            try:
                stack.extend(ch())
            except Exception as e:
                errors.append((type(o).__name__, e))
    if errors:
        raise CloseError(errors)
