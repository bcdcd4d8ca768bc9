"""Vectorized merge join with skip() — the paper's core operator (§3.2).

The classical sort-merge join decomposed into three phases:

  Probe  — find matching *groups*: pairs of (left range, right range) with
           the same join-key value, detected as runs in the sorted key
           columns of the current windows.
  Build  — materialize groups one column at a time: each left value is
           expanded by the right range length, each right range repeated by
           the left range length. Computed slot-parallel as gather indices
           (vecops.expand_cross / kernels join_expand), so the build is a
           pure vector map — the paper's 'column-based cross product, never
           looking at more than one column at a time'.
  Skip   — gallop the side whose last key is smaller via child.skip(),
           exploiting sorted storage (the BARQ contribution over
           CockroachDB's vectorized merge joiner).

Right-side ranges can span batches; the right window accumulates them in an
amortized ring/doubling buffer (append is in-place, trims are head-offset
bumps — no whole-window copies; DESIGN.md §2.3) and spills to disk beyond a
threshold (paper: 'a special collection that can spill off to disk'); a
spilled window trims and gathers without being read back. Emission runs
through the fused gather_emit kernel: gather + NULL-extension + the
vectorized multi-key equality pass (§3.2 Multiple Join Keys) in one
dispatch, writing straight into a pool-recycled output buffer. Modes:
inner, left_outer (OPTIONAL, incl. the per-group all-rows-filtered →
NULL-row case the paper sketches), semi (EXISTS) and anti (MINUS) on the
same machinery.
"""

from __future__ import annotations

import os
import tempfile
from typing import List, Optional, Tuple

import numpy as np

from repro.core import vecops
from repro.core.adaptive import AdaptiveBatchSizer
from repro.core.batch import NULL_ID, BatchPool, ColumnBatch, bucket_for
from repro.core.expressions import eval_expr_mask
from repro.core.exprs import eval_program_mask
from repro.core.operators.base import BatchOperator
from repro.kernels import ops as KOPS

_SPILL_THRESHOLD_ROWS = 1 << 20
_WINDOW_MIN_CAP = 256  # rows; first append sizes the buffer (pow2 doubling)


class _Window:
    """Sorted row window for one side: payload columns keyed by the join
    variable, accumulated across child batches and trimmed as the other
    side advances past keys.

    Implemented as an amortized ring/doubling buffer: live rows occupy
    ``_buf[:, head:tail]``. ``append_batch`` writes in place at the tail
    (compacting to the front or doubling capacity only when out of room, so
    total copy traffic is O(rows appended), not O(rows × batches));
    ``drop_prefix``/``trim_below`` just advance the head. A spilled window
    (memory-mapped) keeps trimming and gathering without being read back —
    only a subsequent append materializes it."""

    def __init__(
        self,
        var_ids: Tuple[int, ...],
        key_var: int,
        spill_dir: Optional[str],
        pool: Optional[BatchPool] = None,
    ):
        self.var_ids = var_ids
        self.key_pos = var_ids.index(key_var)
        self._buf: np.ndarray = np.empty((len(var_ids), 0), dtype=np.int32)
        self._head = 0
        self._tail = 0
        self.exhausted = False
        self.spill_dir = spill_dir
        self.pool = pool  # copy-traffic accounting + recycling of consumed batches
        self._spill_path: Optional[str] = None
        self._spilled = False

    # -- views (no copies) -------------------------------------------------

    @property
    def cols(self) -> np.ndarray:
        """Live rows as an (n_vars, n) view."""
        return self._buf[:, self._head : self._tail]

    @property
    def keys(self) -> np.ndarray:
        return self._buf[self.key_pos, self._head : self._tail]

    @property
    def n(self) -> int:
        return self._tail - self._head

    def last_key(self) -> int:
        return int(self._buf[self.key_pos, self._tail - 1])

    # -- mutation ----------------------------------------------------------

    def append_batch(self, b: ColumnBatch) -> int:
        n = b.n_active
        if n == 0:
            b.release()
            return 0
        self._reserve(n)
        dst = self._buf[:, self._tail : self._tail + n]
        contiguous = n == b.n_rows
        sel = None if contiguous else b.selection_vector()
        for j, v in enumerate(self.var_ids):
            src = b.columns[b.col_index(v)]
            dst[j] = src[:n] if contiguous else src[sel]
        self._tail += n
        if self.pool is not None:
            self.pool.bytes_copied += dst.nbytes
        b.release()
        if (
            self.spill_dir
            and not self._spilled
            and self.n > _SPILL_THRESHOLD_ROWS
        ):
            self._spill()
        return n

    def drop_prefix(self, k: int) -> None:
        if k > 0:
            self._head += k  # O(1); valid for spilled windows too

    def trim_below(self, key: int) -> int:
        """Drop rows with keys < key; returns number dropped."""
        if self.n == 0:
            return 0
        cut = int(np.searchsorted(self.keys, key, side="left"))
        self.drop_prefix(cut)
        return cut

    def gather(self, idx: np.ndarray) -> np.ndarray:
        return np.asarray(self._buf[:, self._head + idx])

    def close(self) -> None:
        if self._spill_path is not None:
            self._buf = np.empty((len(self.var_ids), 0), dtype=np.int32)
            self._head = self._tail = 0
            self._spilled = False
            os.unlink(self._spill_path)
            self._spill_path = None

    # -- internals ---------------------------------------------------------

    def _reserve(self, n: int) -> None:
        if self._spilled:
            self._materialize(extra=n)
        cap = int(self._buf.shape[1])
        if self._tail + n <= cap:
            return
        live = self.n
        if live + n <= cap and self._head >= live:
            # shift live rows to the front (regions don't overlap); the head
            # must clear half the buffer first, so each row is moved O(1)
            # times on average
            self._buf[:, :live] = self._buf[:, self._head : self._tail]
            if self.pool is not None:
                self.pool.bytes_copied += live * len(self.var_ids) * 4
            self._head, self._tail = 0, live
            return
        new_cap = max(cap, _WINDOW_MIN_CAP)
        while new_cap < live + n:
            new_cap *= 2
        nb = np.empty((len(self.var_ids), new_cap), dtype=np.int32)
        nb[:, :live] = self._buf[:, self._head : self._tail]
        if self.pool is not None:
            self.pool.bytes_copied += live * len(self.var_ids) * 4
        self._buf, self._head, self._tail = nb, 0, live

    def _spill(self) -> None:
        fd, path = tempfile.mkstemp(suffix=".npy", dir=self.spill_dir)
        os.close(fd)
        np.save(path, self._buf[:, self._head : self._tail])
        live = self.n
        self._spill_path = path
        self._buf = np.load(path, mmap_mode="r")
        self._head, self._tail = 0, live
        self._spilled = True

    def _materialize(self, extra: int = 0) -> None:
        live = self.n
        cap = _WINDOW_MIN_CAP
        while cap < live + extra:
            cap *= 2
        nb = np.empty((len(self.var_ids), cap), dtype=np.int32)
        nb[:, :live] = np.asarray(self._buf[:, self._head : self._tail])
        if self.pool is not None:
            self.pool.bytes_copied += live * len(self.var_ids) * 4
        self._buf, self._head, self._tail = nb, 0, live
        self._spilled = False
        if self._spill_path is not None:
            os.unlink(self._spill_path)
            self._spill_path = None


class MergeJoin(BatchOperator):
    emits_join_rows = True

    def __init__(
        self,
        left: BatchOperator,
        right: BatchOperator,
        join_var: int,
        mode: str = "inner",
        post_filter=None,  # Expr over materialized rows (OPTIONAL {...} FILTER)
        dictionary=None,
        sizer: Optional[AdaptiveBatchSizer] = None,
        spill_dir: Optional[str] = None,
        allow_child_skip: bool = True,
        pool: Optional[BatchPool] = None,
        post_program=None,  # compiled ExprProgram for post_filter (planner)
    ) -> None:
        assert mode in ("inner", "left_outer", "semi", "anti")
        assert left.sorted_by() == join_var, "left child must be sorted by join var"
        assert right.sorted_by() == join_var, "right child must be sorted by join var"
        self.left = left
        self.right = right
        self.v = join_var
        self.mode = mode
        self.post_filter = post_filter
        self.dictionary = dictionary
        if post_program is False:  # planner: known uncompilable, no retry
            post_program = None
        elif post_program is None and post_filter is not None and dictionary is not None:
            from repro.core.operators.simple import _resolve_program

            post_program = _resolve_program(post_filter, dictionary, None, "mask")
        self.post_program = post_program
        self.sizer = sizer or AdaptiveBatchSizer(initial=256)
        self.allow_child_skip = allow_child_skip
        self.pool = pool

        lv, rv = tuple(left.var_ids()), tuple(right.var_ids())
        self.shared = tuple(x for x in lv if x in rv)
        assert join_var in self.shared
        self.secondary = tuple(x for x in self.shared if x != join_var)
        if mode in ("semi", "anti"):
            self._right_out: Tuple[int, ...] = ()
        else:
            self._right_out = tuple(x for x in rv if x not in lv)
        self._out_vars: Tuple[int, ...] = lv + self._right_out

        # static gather_emit plan: emit all left rows, then the right-only
        # rows; secondary keys become fused equality pairs
        self._lsel = tuple(range(len(lv)))
        self._rsel = tuple(rv.index(x) for x in self._right_out)
        self._pairs = tuple((lv.index(sv), rv.index(sv)) for sv in self.secondary)

        self._lwin = _Window(lv, join_var, None, pool)
        self._rwin = _Window(rv, join_var, spill_dir, pool)
        self._lmatched = np.zeros(0, dtype=bool)  # aligned with left window
        # pending build: (lstarts, llens, rstarts, rlens, cum, emitted)
        self._pending: Optional[Tuple] = None
        self._finalize_l_hi: Optional[int] = None
        self._leftover_queue: List[np.ndarray] = []  # (n_lvars, n) row blocks
        self._done = False
        # does matched-tracking require materialization?
        self._needs_expansion_for_match = bool(self.secondary) or post_filter is not None
        super().__init__("MergeJoin", f"(?v{join_var}) mode={mode}")

    # -- metadata ---------------------------------------------------------------

    def var_ids(self) -> Tuple[int, ...]:
        return self._out_vars

    def sorted_by(self) -> Optional[int]:
        # left_outer interleaves NULL-extended rows after each probe window,
        # breaking global key order; inner/semi/anti preserve it.
        return None if self.mode == "left_outer" else self.v

    def children(self) -> List[BatchOperator]:
        return [self.left, self.right]

    # -- iteration ----------------------------------------------------------------

    def _next(self) -> Optional[ColumnBatch]:
        cap = bucket_for(self.sizer.on_next())
        while True:
            if self._pending is not None:
                out = self._emit_pending(cap)
                if self._pending is None and self._finalize_l_hi is not None:
                    self._finalize_probe()
                if out is not None and out.n_active > 0:
                    return out
                continue
            if self._finalize_l_hi is not None:
                self._finalize_probe()
                continue
            if self._leftover_queue:
                return self._emit_leftovers(cap)
            if self._done:
                return None
            if not self._advance():
                self._done = True

    def _skip(self, var: int, target: int) -> None:
        if var != self.v:
            raise ValueError("skip on non-join var")
        self._pending = None
        self._finalize_l_hi = None
        self._leftover_queue.clear()
        dropped = self._lwin.trim_below(target)
        self._lmatched = self._lmatched[dropped:]
        self._rwin.trim_below(target)
        if self.left.supports_skip():
            self.left.skip(self.v, target)
        if self.right.supports_skip():
            self.right.skip(self.v, target)

    def _close(self) -> None:
        # _Window.close is idempotent, so teardown after _reset (or a
        # second close from an outer finally) is safe
        self._lwin.close()
        self._rwin.close()

    def _reset(self) -> None:
        self.left.reset()
        self.right.reset()
        self._lwin.close()
        self._rwin.close()
        self._lwin = _Window(self._lwin.var_ids, self.v, None, self.pool)
        self._rwin = _Window(self._rwin.var_ids, self.v, self._rwin.spill_dir, self.pool)
        self._lmatched = np.zeros(0, dtype=bool)
        self._pending = None
        self._finalize_l_hi = None
        self._leftover_queue.clear()
        self._done = False

    # -- fetch helpers -------------------------------------------------------------

    def _fetch_left(self) -> bool:
        if self._lwin.exhausted:
            return False
        b = self.left.next_batch()
        if b is None:
            self._lwin.exhausted = True
            return False
        grown = self._lwin.append_batch(b)
        if grown:
            self._lmatched = np.concatenate(
                [self._lmatched, np.zeros(grown, dtype=bool)]
            )
        return True

    def _fetch_right(self) -> bool:
        if self._rwin.exhausted:
            return False
        b = self.right.next_batch()
        if b is None:
            self._rwin.exhausted = True
            return False
        self._rwin.append_batch(b)
        return True

    # -- state machine ----------------------------------------------------------------

    def _advance(self) -> bool:
        """Create new work (a pending build or queued leftovers).
        Returns False when fully exhausted."""
        while self._lwin.n == 0:
            if not self._fetch_left():
                return False
        while self._rwin.n == 0 and not self._rwin.exhausted:
            self._fetch_right()

        if self._rwin.n == 0:  # right side is empty and exhausted
            if self.mode in ("left_outer", "anti"):
                # every remaining left row is unmatched
                self._probe(self._lwin.n)
                return True
            return False

        # Probe boundary: right runs with key < the window's last key are
        # complete; the last run may continue into the next right batch
        # (unless the right side is exhausted).
        if self._rwin.exhausted:
            l_hi = self._lwin.n
        else:
            r_boundary = self._rwin.last_key()
            l_hi = int(np.searchsorted(self._lwin.keys, r_boundary, side="left"))

        if l_hi > 0:
            self._probe(l_hi)
            return True

        # Left frontier is at/above the right boundary: grow the right window.
        l_first = int(self._lwin.keys[0])
        if self.allow_child_skip and self.right.supports_skip() and self._rwin.last_key() < l_first:
            # Skip phase: gallop right to the left frontier (paper 3.a)
            self.right.skip(self.v, l_first)
        self._fetch_right()
        return True

    def _probe(self, l_hi: int) -> None:
        """Probe left rows [0, l_hi) against the right window; queue the
        build. Finalization (matched bookkeeping + trims) happens after the
        build is fully emitted."""
        lkeys = self._lwin.keys[:l_hi]
        lvals, lstarts, llens = vecops.run_boundaries(lkeys)
        rvals, rstarts, rlens = vecops.run_boundaries(self._rwin.keys)
        gl, gr = vecops.probe_groups(lvals, rvals)

        if len(gl) and not self._needs_expansion_for_match:
            # fast path: primary-key membership decides matched. The ranges
            # are marked with a +1/-1 boundary diff + running sum instead of
            # a per-group Python loop.
            d = np.zeros(l_hi + 1, dtype=np.int32)
            ls, ll = lstarts[gl], llens[gl]
            np.add.at(d, ls, 1)
            np.add.at(d, ls + ll, -1)
            np.logical_or(
                self._lmatched[:l_hi], np.cumsum(d[:-1]) > 0,
                out=self._lmatched[:l_hi],
            )

        need_build = len(gl) > 0 and (
            self.mode in ("inner", "left_outer") or self._needs_expansion_for_match
        )
        if need_build:
            g_ls, g_ll = lstarts[gl], llens[gl]
            g_rs, g_rl = rstarts[gr], rlens[gr]
            cum = vecops.group_output_offsets(g_ll, g_rl)
            if int(cum[-1]) > 0:
                self._pending = (g_ls, g_ll, g_rs, g_rl, cum, 0)
        self._finalize_l_hi = l_hi

    def _finalize_probe(self) -> None:
        l_hi = self._finalize_l_hi
        self._finalize_l_hi = None
        if self.mode == "semi":
            sel = np.nonzero(self._lmatched[:l_hi])[0].astype(np.int32)
            if len(sel):
                self._leftover_queue.append(self._lwin.gather(sel))
        elif self.mode in ("left_outer", "anti"):
            um = np.nonzero(~self._lmatched[:l_hi])[0].astype(np.int32)
            if len(um):
                self._leftover_queue.append(self._lwin.gather(um))

        self._lwin.drop_prefix(l_hi)
        self._lmatched = self._lmatched[l_hi:]

        if self._lwin.n > 0:
            self._rwin.trim_below(int(self._lwin.keys[0]))
        elif not self._lwin.exhausted:
            # Skip phase: gallop left to the right frontier (inner/semi only —
            # outer/anti must still observe unmatched left rows)
            if (
                self._rwin.n > 0
                and self.allow_child_skip
                and self.mode in ("inner", "semi")
                and self.left.supports_skip()
            ):
                self.left.skip(self.v, int(self._rwin.keys[0]))
            self._fetch_left()
            if self._lwin.n > 0:
                self._rwin.trim_below(int(self._lwin.keys[0]))

    # -- emission ----------------------------------------------------------------

    def _emit_pending(self, cap: int) -> Optional[ColumnBatch]:
        g_ls, g_ll, g_rs, g_rl, cum, emitted = self._pending
        total = int(cum[-1])
        count = min(cap, total - emitted)
        li, ri = KOPS.join_expand(g_ls, g_ll, g_rs, g_rl, cum, emitted, count)
        emitted += count
        self._pending = None if emitted >= total else (g_ls, g_ll, g_rs, g_rl, cum, emitted)

        if self.mode in ("semi", "anti") and self.post_filter is None:
            # expansion only feeds matched-tracking: fused mask, no columns
            _, mask = KOPS.gather_emit(
                self._lwin.cols, self._rwin.cols, li, ri, (), (), self._pairs
            )
            if mask.any():
                self._lmatched[li[mask]] = True
            return None

        b = ColumnBatch.alloc(
            self._out_vars, bucket_for(max(count, 1)), self.pool, self.v
        )
        _, mask = KOPS.gather_emit(
            self._lwin.cols, self._rwin.cols, li, ri,
            self._lsel, self._rsel, self._pairs, out=b.columns,
        )
        b.n_rows = count
        if count < b.capacity:
            b.columns[:, count:] = NULL_ID
        b.mask[:count] = mask
        if self.pool is not None:
            self.pool.bytes_copied += len(self._out_vars) * count * 4
        if self.post_filter is not None:
            # OPTIONAL {...} FILTER condition: fused VM program when the
            # planner compiled one, interpreted walk otherwise
            if self.post_program is not None:
                b = b.with_mask(
                    eval_program_mask(self.post_program, b, self.dictionary)
                )
            else:
                b = b.with_mask(eval_expr_mask(self.post_filter, b, self.dictionary))

        if self._needs_expansion_for_match:
            surv = b.mask[:count]
            if surv.any():
                self._lmatched[li[surv]] = True

        if self.mode in ("semi", "anti"):
            b.release()
            return None  # expansion only feeds matched-tracking
        if b.n_active:
            return b
        b.release()
        return None

    def _emit_leftovers(self, cap: int) -> ColumnBatch:
        rows = self._leftover_queue.pop(0)
        n = rows.shape[1]
        if n > cap:
            self._leftover_queue.insert(0, rows[:, cap:])
            rows = rows[:, :cap]
            n = cap
        out_cols = [rows[i] for i in range(rows.shape[0])]
        for _ in self._right_out:
            out_cols.append(np.full(n, NULL_ID, dtype=np.int32))
        return ColumnBatch.from_columns(self._out_vars, out_cols, self.v, pool=self.pool)
