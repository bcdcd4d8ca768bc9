"""Index scan operator: evaluates one triple pattern over a sorted index.

Produces columnar batches sorted by the first free role of the chosen index
order. Supports ``skip()`` on that role (the storage seek), drives the
adaptive batch sizer from the received next()/skip() pattern (paper §3.4),
and counts rows read from storage so benchmarks can report overfetching.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import telemetry
from repro.core.adaptive import AdaptiveBatchSizer
from repro.core.algebra import K, TriplePattern, V
from repro.core.batch import BatchPool, ColumnBatch
from repro.core.operators.base import BatchOperator
from repro.core.sip import SipFilter
from repro.core.storage import INDEX_ORDERS, QuadStore, ScanRange

_ROLE_NAMES = ("s", "p", "o", "g")

# A scan under k SIP build keys whose code range holds n rows seeks the
# keys' runs when k * SEEK_ROWS_PER_KEY < n + SEEK_FIXED_ROWS. On the numpy
# plane one key's seek (two binary searches and a gather) costs what the
# range path pays for about 8 rows (a copy, a bloom hash, the key's share of
# the bloom build), and the range path's fixed cost (one bloom build, at
# least one probe) what it pays for about 4,096. A device plane only raises
# the range path's side: a round trip per 4,096 rows (DESIGN.md §12.2).
SEEK_ROWS_PER_KEY = 8
SEEK_FIXED_ROWS = 4096


class IndexScan(BatchOperator):
    def __init__(
        self,
        store: QuadStore,
        pattern: TriplePattern,
        want_sorted_var: Optional[int] = None,
        sizer: Optional[AdaptiveBatchSizer] = None,
        detail: str = "",
        pool: Optional[BatchPool] = None,
        sip_filters: Sequence[SipFilter] = (),
    ) -> None:
        self.store = store
        self.pattern = pattern
        self.pool = pool
        # sideways-information-passing prefilters (DESIGN.md §12): each is
        # a keys/bloom/range summary of some downstream join's build side.
        # On the sorted var they seek the build keys' runs, or narrow the
        # scan to their code range; on other vars they mask batches.
        # Applied lazily on the first next() so the exporting join's build
        # phase has run by then.
        self.sip_filters = list(sip_filters)
        self._sip_pending = bool(self.sip_filters)
        self._sip_hi: Optional[int] = None
        self._mask_filters: List[SipFilter] = self.sip_filters
        # key-seek mode: offsets (within the range) of the rows of the build
        # keys' runs and their sort keys; ``offset`` then indexes these
        self._seek_rows: Optional[np.ndarray] = None
        self._seek_keys: Optional[np.ndarray] = None

        # encode constant slots; a constant not present in the dictionary
        # means the pattern matches nothing
        self._dead = False
        bound: List[Optional[int]] = [None, None, None, None]
        slots = (pattern.s, pattern.p, pattern.o, pattern.g)
        for role, sl in enumerate(slots):
            if isinstance(sl, K):
                tid = store.dict.lookup(sl.term)
                if tid is None:
                    self._dead = True
                    tid = -1
                bound[role] = tid
        self.bound = bound

        # free roles and their variables; repeated vars inside one pattern
        # (e.g. ?x :p ?x) add a residual equality mask
        self.role_of_var: Dict[int, int] = {}
        self.residual_pairs: List[Tuple[int, int]] = []  # (role_a, role_b)
        for role, sl in enumerate(slots):
            if isinstance(sl, V):
                if sl.id in self.role_of_var:
                    self.residual_pairs.append((self.role_of_var[sl.id], role))
                else:
                    self.role_of_var[sl.id] = role

        want_role = self.role_of_var.get(want_sorted_var) if want_sorted_var is not None else None
        self.index = store.choose_index(bound, want_role)
        self.perm = INDEX_ORDERS[self.index]

        # column position (within the index order) of each output variable
        self._var_ids = tuple(self.role_of_var)
        self.var_col_pos = {
            v: self.perm.index(self.role_of_var[v]) for v in self._var_ids
        }
        # sortedness: the first free position in the index order
        n_bound = 0
        while n_bound < 4 and bound[self.perm[n_bound]] is not None:
            n_bound += 1
        self._sort_col_pos = n_bound if n_bound < 4 else None
        self._sorted_var: Optional[int] = None
        if self._sort_col_pos is not None:
            role = self.perm[self._sort_col_pos]
            for v, r in self.role_of_var.items():
                if r == role:
                    self._sorted_var = v

        self.range: ScanRange = (
            ScanRange(self.index, 0, 0)
            if self._dead
            else store.range_for_pattern(self.index, bound)
        )
        self.offset = 0
        self.sizer = sizer or AdaptiveBatchSizer()
        super().__init__("Scan", detail or self._describe())

    def _describe(self) -> str:
        parts = []
        slots = (self.pattern.s, self.pattern.p, self.pattern.o)
        for sl in slots:
            parts.append(f"?v{sl.id}" if isinstance(sl, V) else str(sl.term))
        return f"({', '.join(parts)}) [{self.index}]"

    # -- operator API -----------------------------------------------------------

    def var_ids(self) -> Tuple[int, ...]:
        return self._var_ids

    def sorted_by(self) -> Optional[int]:
        return self._sorted_var

    def _next(self) -> Optional[ColumnBatch]:
        if self._sip_pending:
            self._apply_sip_ranges()
        while True:
            rows = self._read()
            if rows is None:
                return None
            cols = [rows[:, self.var_col_pos[v]] for v in self._var_ids]
            b = ColumnBatch.from_columns(
                self._var_ids, cols, self._sorted_var, pool=self.pool
            )
            for ra, rb in self.residual_pairs:
                pa, pb = self.perm.index(ra), self.perm.index(rb)
                m = np.zeros(b.capacity, dtype=bool)
                m[: b.n_rows] = rows[:, pa] == rows[:, pb]
                b = b.with_mask(m)
            b = self._apply_sip_masks(b)
            if b.n_active or self.offset >= self._n_positions():
                return b
            # fully pruned by SIP: read the next chunk instead of bouncing
            # an empty batch up the pipeline
            b.release()

    def _n_positions(self) -> int:
        return len(self.range) if self._seek_rows is None else len(self._seek_rows)

    def _read(self) -> Optional[np.ndarray]:
        """The next chunk of rows in index order (the sizer's batch size),
        counted as read; None once the scan is done."""
        if self.offset >= self._n_positions():
            return None
        count = self.sizer.on_next()
        if self._seek_rows is not None:
            rows = self.store.take(self.range, self._seek_rows[self.offset : self.offset + count])
        else:
            rows = self.store.read(self.range, self.offset, count)
        self.offset += len(rows)
        self.stats.rows_scanned += len(rows)
        if self.sip_filters:
            telemetry.count_sip_scan(len(rows))
        if self._sip_hi is not None and len(rows):
            keys = rows[:, self._sort_col_pos]
            if keys[0] > self._sip_hi:
                # galloped past the build-side range: the scan is done
                self.offset = len(self.range)
                return None
            if keys[-1] > self._sip_hi:
                rows = rows[: int(np.searchsorted(keys, self._sip_hi, "right"))]
                self.offset = len(self.range)
        return rows

    # -- sideways information passing (DESIGN.md §12) ---------------------------

    def _apply_sip_ranges(self) -> None:
        """Narrowing on the sorted var, once, before the first read: seek
        to the build side's min key and stop past its max — the skip()
        machinery applied sideways instead of from a parent — and, where
        the build keys are sparse in that range, read only their runs."""
        self._sip_pending = False
        sorted_filters = []
        for f in self.sip_filters:
            if not self.can_skip(f.var):
                continue  # unsorted var: mask-mode only (no exceptions)
            rng = f.code_range()
            if rng is None:
                continue
            if rng[1] < rng[0]:  # provably empty build side: nothing can match
                self.offset = len(self.range)
                return
            self.offset = self.store.seek(
                self.range, self.offset, self._sort_col_pos, rng[0]
            )
            self._sip_hi = rng[1] if self._sip_hi is None else min(self._sip_hi, rng[1])
            self.stats.extra["sip_range_seeks"] = (
                self.stats.extra.get("sip_range_seeks", 0) + 1
            )
            sorted_filters.append(f)
        if not sorted_filters:
            return
        stop = self.store.seek(
            self.range, self.offset, self._sort_col_pos, self._sip_hi, "right"
        )
        if stop == self.offset:  # no row inside the code range
            self.offset = len(self.range)
            return
        keyed = sorted((f for f in sorted_filters if f.keys is not None),
                       key=lambda f: len(f.keys))
        if not keyed:
            return
        # the shortest key list, cut to the other keyed filters' keys: with
        # the code range above, each filter on the sorted var is then exact
        keys = keyed[0].keys
        for f in keyed[1:]:
            at = np.minimum(np.searchsorted(f.keys, keys), len(f.keys) - 1)
            keys = keys[f.keys[at] == keys]
        if len(keys) * SEEK_ROWS_PER_KEY >= stop - self.offset + SEEK_FIXED_ROWS:
            return  # dense keys: range + bloom masks
        starts, ends = self.store.seek_keys(
            self.range, self.offset, stop, self._sort_col_pos, keys
        )
        lens = ends - starts
        n = int(lens.sum())
        # run i's rows: starts[i] .. ends[i]-1, laid end to end
        self._seek_rows = np.arange(n) + np.repeat(starts - (np.cumsum(lens) - lens), lens)
        self._seek_keys = np.repeat(keys, lens)
        self.offset = 0
        self._sip_hi = None
        self._mask_filters = [f for f in self.sip_filters if f not in sorted_filters]
        self.stats.extra["sip_key_seeks"] = self.stats.extra.get("sip_key_seeks", 0) + 1
        telemetry.count_sip_scan(0, seeks=1)

    def _apply_sip_masks(self, b: ColumnBatch) -> ColumnBatch:
        for f in self._mask_filters:
            m = f.mask(b.columns[b.col_index(f.var), : b.n_rows])
            if m is None:
                continue
            full = np.ones(b.capacity, dtype=bool)
            full[: b.n_rows] = m
            b = b.with_mask(full)
        if self.sip_filters:
            self.stats.extra["sip_pruned_rows"] = sum(
                f.rows_pruned for f in self.sip_filters
            )
            self.stats.extra["sip_probe_dispatches"] = sum(
                f.probe_dispatches for f in self.sip_filters
            )
        return b

    def can_skip(self, var: Optional[int]) -> bool:
        return (
            var is not None
            and var == self._sorted_var
            and self._sort_col_pos is not None
        )

    def _skip(self, var: int, target: int) -> None:
        if not self.can_skip(var):
            raise ValueError("skip on unsorted variable")
        self.sizer.on_skip()
        if self._seek_rows is not None:
            at = int(np.searchsorted(self._seek_keys, target))
            self.offset = max(self.offset, at)
            return
        self.offset = self.store.seek(
            self.range, self.offset, self._sort_col_pos, target
        )

    def _reset(self) -> None:
        self.offset = 0
        self.sizer.on_reset()
        self._sip_pending = bool(self.sip_filters)
        self._sip_hi = None
        self._mask_filters = self.sip_filters
        self._seek_rows = self._seek_keys = None

    # cardinality for the planner
    def estimated_rows(self) -> int:
        return len(self.range)

    def sip_code_range(self) -> Tuple[int, int]:
        """Inclusive (lo, hi) of the sort column over the whole range —
        O(1) off the sorted index, the range-only SipFilter payload a
        merely-sorted merge-join build side can export without
        materializing. (0, -1) when the scan is empty."""
        n = len(self.range)
        if n == 0 or self._sort_col_pos is None:
            return 0, -1
        first = self.store.read(self.range, 0, 1)[0, self._sort_col_pos]
        last = self.store.read(self.range, n - 1, 1)[0, self._sort_col_pos]
        return int(first), int(last)
