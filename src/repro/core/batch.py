"""Columnar solution batches — the BARQ data unit (paper §3.1).

A batch holds one int32 column per query variable (dictionary-encoded RDF
term IDs) plus a validity mask. The paper uses a *selection vector* (sorted
dense position list of active rows); on TPU the idiomatic carrier is a
bitmask, because masked SIMD lanes are free while SV indirection implies
gathers (see DESIGN.md §2). ``selection_vector()`` materializes the paper's
representation on demand (used at materialization boundaries and by the
batch→row adapter).

Shapes are static per capacity bucket so every per-batch kernel compiles
once per (n_vars, capacity) signature. Buffers are recycled through a
``BatchPool`` arena keyed by that same signature (DESIGN.md §2.3): on the
steady state a query's data plane performs zero buffer allocations — each
operator's output batches reuse the buffers its consumer released.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

# NULL marker constant (paper §3.1 "NULLs"): OPTIONAL can leave variables
# unbound inside an aligned batch. Valid dictionary IDs are >= 0.
NULL_ID = np.int32(-1)

# Pool-sanitizer hook point (DESIGN.md §16). None until the first
# SanitizingBatchPool is constructed (repro.analysis.sanitize installs its
# tracker here); every lifecycle hook below is a single ``is None`` test
# when sanitizing is off, and batches of plain pools stay untracked even
# when it is on.
_SANITIZER = None

# Power-of-two capacity buckets (paper: adaptive batch size <= 512; we keep
# the same spirit with a bounded set of compiled shapes, DESIGN.md §2).
MIN_BATCH = 32
MAX_BATCH = 4096
BATCH_BUCKETS: Tuple[int, ...] = tuple(
    1 << p for p in range(MIN_BATCH.bit_length() - 1, MAX_BATCH.bit_length())
)


# Weighted counting (DESIGN.md §17): a COUNT(*) plan carries each row's
# multiplicity. A fold's int64 weight w rides as two int32 columns, the
# pair (w mod 2^31, w >> 31), under var ids WEIGHT_VAR + 2k and + 2k + 1,
# so every operator carries it as it carries any column. A row's weight is
# the product of its pairs; a row without pairs, or whose pair is unbound
# (OPTIONAL with no match, the other branch of a UNION), weighs 1.
WEIGHT_VAR = 1 << 30
_LOW_BITS = (1 << 31) - 1


def is_weight_var(v: int) -> bool:
    return v >= WEIGHT_VAR


def row_weights(var_ids: Sequence[int], cols: np.ndarray) -> Optional[np.ndarray]:
    """Each row's int64 weight of an (n_vars, n) column block, or None
    where the block carries no weight pair (every row weighs 1)."""
    w = None
    for i, v in enumerate(var_ids):
        if v >= WEIGHT_VAR and (v - WEIGHT_VAR) % 2 == 0:
            lo = cols[i].astype(np.int64)
            hi = cols[list(var_ids).index(v + 1)].astype(np.int64)
            f = np.where(lo == NULL_ID, 1, lo + (hi << 31))
            w = f if w is None else w * f
    return w


def weight_pair(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64 weights (1 <= w < 2^62) as their two int32 columns."""
    return (w & _LOW_BITS).astype(np.int32), (w >> 31).astype(np.int32)


def bucket_for(n: int) -> int:
    """Smallest capacity bucket holding ``n`` rows."""
    for b in BATCH_BUCKETS:
        if n <= b:
            return b
    return MAX_BATCH


class BatchPool:
    """Arena of recycled batch buffers, keyed by (n_vars, capacity).

    The release()/acquire() cycle makes steady-state execution
    allocation-free: the number of fresh allocations is bounded by the
    number of batches simultaneously alive, which is O(plan depth), not
    O(batches emitted) (DESIGN.md §2.3). ``drain()`` returns the arena's
    memory at end of query.

    Counters feed the profiler: ``allocations``/``bytes_allocated`` count
    fresh numpy buffers, ``reuses`` recycled ones, and ``bytes_copied`` is
    credited by the join windows / concat paths for every byte of column
    data they physically move.
    """

    def __init__(self, max_per_bucket: int = 32) -> None:
        self.max_per_bucket = max_per_bucket
        self._free: Dict[Tuple[int, int], List[Tuple[np.ndarray, np.ndarray]]] = {}
        self.allocations = 0
        self.reuses = 0
        self.releases = 0
        # fresh buffers permanently retired: returned over a full stack, or
        # swept by drain(). Feeds the counters() conservation law.
        self.dropped = 0
        self.bytes_allocated = 0
        self.bytes_copied = 0

    def acquire(self, n_vars: int, capacity: int) -> Tuple[np.ndarray, np.ndarray]:
        """A (columns, mask) buffer pair; contents are UNINITIALIZED."""
        stack = self._free.get((n_vars, capacity))
        if stack:
            self.reuses += 1
            return stack.pop()
        self.allocations += 1
        cols = np.empty((n_vars, capacity), dtype=np.int32)
        mask = np.empty(capacity, dtype=bool)
        self.bytes_allocated += cols.nbytes + mask.nbytes
        return cols, mask

    def release(self, cols: np.ndarray, mask: np.ndarray) -> None:
        self.releases += 1
        key = (int(cols.shape[0]), int(cols.shape[1]))
        stack = self._free.setdefault(key, [])
        if len(stack) < self.max_per_bucket:
            stack.append((cols, mask))
        else:
            self.dropped += 1

    def drain(self) -> None:
        """Drop every recycled buffer (end-of-query teardown)."""
        self.dropped += sum(len(s) for s in self._free.values())
        self._free.clear()

    def stats(self) -> Dict[str, int]:
        return {
            "allocations": self.allocations,
            "reuses": self.reuses,
            "releases": self.releases,
            "bytes_allocated": self.bytes_allocated,
            "bytes_copied": self.bytes_copied,
        }

    def counters(self) -> Dict[str, int]:
        """Buffer conservation snapshot (DESIGN.md §16): every fresh buffer
        is live (owned by a batch), pooled (in a free stack), or retired
        — so after a query fully drains its operators,
        ``allocs == releases + pooled`` and ``live == 0``."""
        pooled = sum(len(s) for s in self._free.values())
        return {
            "allocs": self.allocations,
            "releases": self.dropped,
            "pooled": pooled,
            "live": self.allocations - self.dropped - pooled,
            "acquires": self.allocations + self.reuses,
            "recycles": self.releases,
        }


@dataclasses.dataclass
class ColumnBatch:
    """A batch of solutions in columnar layout.

    Attributes:
      var_ids:  static tuple of variable ids, one per column (sorted order
                not required; position is the column index).
      columns:  int32 array of shape (n_vars, capacity).
      mask:     bool array (capacity,) — True for active rows. The TPU
                carrier for the paper's selection vector.
      n_rows:   number of *physically filled* rows (<= capacity). Rows in
                [n_rows, capacity) are padding and always masked out.
      sorted_by: var id the active rows are non-decreasing in, or None.
      pool:     owning BatchPool, or None for unpooled buffers. Exactly one
                holder owns the buffers; transforms that share them
                (with_mask) MOVE ownership to the derived batch. The final
                consumer calls release() after copying data out.
    """

    var_ids: Tuple[int, ...]
    columns: np.ndarray
    mask: np.ndarray
    n_rows: int
    sorted_by: Optional[int] = None
    pool: Optional[BatchPool] = None

    # -- constructors -----------------------------------------------------

    @staticmethod
    def from_columns(
        var_ids: Sequence[int],
        cols: Sequence[np.ndarray],
        sorted_by: Optional[int] = None,
        capacity: Optional[int] = None,
        pool: Optional[BatchPool] = None,
    ) -> "ColumnBatch":
        var_ids = tuple(int(v) for v in var_ids)
        n = int(cols[0].shape[0]) if cols else 0
        cap = capacity or bucket_for(max(n, 1))
        if pool is not None:
            # pool-aware fast path: write into a recycled buffer instead of
            # zero-filling a fresh one (DESIGN.md §2.3)
            data, mask = pool.acquire(len(var_ids), cap)
            mask[:n] = True
            mask[n:] = False
        else:
            data = np.full((len(var_ids), cap), NULL_ID, dtype=np.int32)
            mask = np.zeros(cap, dtype=bool)
            mask[:n] = True
        for i, c in enumerate(cols):
            data[i, :n] = np.asarray(c, dtype=np.int32)
        if pool is not None and n < cap:
            data[:, n:] = NULL_ID  # deterministic padding on recycled memory
        b = ColumnBatch(var_ids, data, mask, n, sorted_by, pool)
        if pool is not None and _SANITIZER is not None:
            _SANITIZER.on_create(b)
        return b

    @staticmethod
    def alloc(
        var_ids: Sequence[int],
        capacity: int,
        pool: Optional[BatchPool] = None,
        sorted_by: Optional[int] = None,
    ) -> "ColumnBatch":
        """A writable batch for kernel emit paths: columns content is
        undefined, mask is all-False, n_rows is 0. The writer fills
        columns[:, :n], sets mask[:n] and n_rows, and must NULL-fill
        columns[:, n:] when it stops short of capacity."""
        var_ids = tuple(int(v) for v in var_ids)
        if pool is not None:
            data, mask = pool.acquire(len(var_ids), capacity)
            mask[:] = False
        else:
            data = np.full((len(var_ids), capacity), NULL_ID, dtype=np.int32)
            mask = np.zeros(capacity, dtype=bool)
        b = ColumnBatch(var_ids, data, mask, 0, sorted_by, pool)
        if pool is not None and _SANITIZER is not None:
            _SANITIZER.on_create(b)
        return b

    @staticmethod
    def empty(var_ids: Sequence[int], capacity: int = MIN_BATCH) -> "ColumnBatch":
        var_ids = tuple(int(v) for v in var_ids)
        data = np.full((len(var_ids), capacity), NULL_ID, dtype=np.int32)
        return ColumnBatch(var_ids, data, np.zeros(capacity, dtype=bool), 0, None)

    # -- pooling ----------------------------------------------------------

    def release(self) -> None:
        """Return the buffers to the owning pool. Idempotent; no-op for
        unpooled batches. The caller must not touch columns/mask after."""
        pool, self.pool = self.pool, None
        if pool is not None:
            if _SANITIZER is not None:
                _SANITIZER.on_release(self)
            if getattr(pool, "_sanitized", False):
                # only [:, :n_rows] ever held exposed data; poisoning just
                # that region keeps the release cost proportional to use
                pool.release(self.columns, self.mask, used=self.n_rows)
            else:
                pool.release(self.columns, self.mask)

    def _guard(self) -> None:
        """Use-after-release tripwire: raises SanitizeError when the
        sanitizer is installed and this batch's buffers were released or
        MOVEd. A single global ``is None`` test otherwise; the tombstone
        probe is inlined so tracked-but-live batches stay cheap."""
        if _SANITIZER is not None and self.__dict__.get("_san_state") is not None:
            _SANITIZER.on_access(self)

    # -- accessors ---------------------------------------------------------

    @property
    def capacity(self) -> int:
        return int(self.columns.shape[1])

    @property
    def n_active(self) -> int:
        self._guard()
        return int(self.mask[: self.n_rows].sum()) if self.n_rows else 0

    def col_index(self, var: int) -> int:
        return self.var_ids.index(var)

    def column(self, var: int) -> np.ndarray:
        """Raw (uncompacted) column including inactive rows."""
        self._guard()
        return self.columns[self.col_index(var), : self.n_rows]

    def selection_vector(self) -> np.ndarray:
        """The paper's SV: sorted dense indices of active rows."""
        self._guard()
        return np.nonzero(self.mask[: self.n_rows])[0].astype(np.int32)

    def active_column(self, var: int) -> np.ndarray:
        return self.column(var)[self.mask[: self.n_rows]]

    def weight_total(self, n_active: int) -> int:
        """Σ of the active rows' weights: ``n_active``, their number, where
        the batch carries no weight pair."""
        if max(self.var_ids, default=-1) < WEIGHT_VAR:
            return n_active
        self._guard()
        w = row_weights(self.var_ids, self.columns[:, : self.n_rows])
        return int(w[self.mask[: self.n_rows]].sum())

    # -- transforms ----------------------------------------------------------

    def compact(self) -> "ColumnBatch":
        """Drop inactive rows (materialization boundary). Buffer ownership
        moves to the compacted batch; when rows are actually dropped the
        source buffers are recycled (fancy indexing copied the data out)."""
        self._guard()
        if self.n_active == self.n_rows:
            return self
        sel = self.selection_vector()
        cols = [self.columns[i, sel] for i in range(len(self.var_ids))]
        out = ColumnBatch.from_columns(self.var_ids, cols, self.sorted_by, pool=self.pool)
        self.release()
        return out

    def project(self, keep: Sequence[int]) -> "ColumnBatch":
        keep = tuple(int(v) for v in keep)
        idx = [self.col_index(v) for v in keep]
        sb = self.sorted_by if self.sorted_by in keep else None
        # row fancy-indexing copies, so the projected batch is unpooled and
        # this batch keeps ownership of its buffers; the mask is only shared
        # when that ownership can't be released out from under the copy
        m = self.mask if self.pool is None else self.mask.copy()
        return ColumnBatch(keep, self.columns[idx], m, self.n_rows, sb)

    def with_mask(self, mask: np.ndarray) -> "ColumnBatch":
        self._guard()
        if self.pool is not None:
            # pooled batches are single-owner: narrow the mask in place and
            # MOVE buffer ownership to the derived batch (zero-copy)
            np.logical_and(self.mask, mask, out=self.mask)
            pool, self.pool = self.pool, None
            out = ColumnBatch(
                self.var_ids, self.columns, self.mask, self.n_rows, self.sorted_by, pool
            )
            if _SANITIZER is not None:
                _SANITIZER.on_move(self, out)
            return out
        m = self.mask & mask
        return ColumnBatch(self.var_ids, self.columns, m, self.n_rows, self.sorted_by)

    def rows(self) -> Iterable[Dict[int, int]]:
        """Row-major view (the batch→row adapter uses this; copy-free per
        the paper §4.2 — values are read straight out of the columns)."""
        self._guard()
        for r in range(self.n_rows):
            if self.mask[r]:
                yield {
                    v: int(self.columns[i, r])
                    for i, v in enumerate(self.var_ids)
                    if self.columns[i, r] != NULL_ID
                }

    def to_rows_array(self) -> np.ndarray:
        """Active rows as (n_active, n_vars) int32 — for tests/oracles."""
        self._guard()
        sel = self.selection_vector()
        return self.columns[:, sel].T.copy()


def concat_batches(
    batches: Sequence[ColumnBatch],
    var_ids: Optional[Sequence[int]] = None,
    pool: Optional[BatchPool] = None,
    release_inputs: bool = False,
) -> ColumnBatch:
    """Concatenate batches, aligning schemas and NULL-filling missing vars.

    Built on the fused gather_emit primitive: each input batch is gathered
    straight into the output buffer at its offset (one pass per source, no
    intermediate per-column materialization). With ``pool``, the output
    buffer is recycled; with ``release_inputs``, consumed batches return
    their buffers to the pool."""
    from repro.core import vecops

    if not batches:
        return ColumnBatch.empty(tuple(var_ids or ()))
    if var_ids is None:
        seen: Dict[int, None] = {}
        for b in batches:
            for v in b.var_ids:
                seen.setdefault(v, None)
        var_ids = tuple(seen)
    var_ids = tuple(int(v) for v in var_ids)
    total = sum(b.n_active for b in batches)
    # bucket capacities top out at MAX_BATCH; a materialization-sized concat
    # gets an exact-size buffer instead of a silently clipped one
    cap = bucket_for(max(total, 1))
    if total > cap:
        cap = total
    out = ColumnBatch.alloc(var_ids, cap, pool)
    pos = 0
    for b in batches:
        sel = b.selection_vector()
        n = len(sel)
        if n:
            src_rows = tuple(
                b.var_ids.index(v) if v in b.var_ids else -1 for v in var_ids
            )
            vecops.gather_emit(
                b.columns, None, sel, None, src_rows, (), (),
                out=out.columns, out_offset=pos,
            )
            if pool is not None:  # NULL-filled missing vars aren't copies
                pool.bytes_copied += sum(1 for r in src_rows if r >= 0) * n * 4
            pos += n
        if release_inputs:
            b.release()
    if total < cap:
        out.columns[:, total:] = NULL_ID
    out.mask[:total] = True
    out.n_rows = total
    return out
