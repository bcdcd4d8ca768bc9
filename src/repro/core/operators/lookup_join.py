"""Lookup join: sort-based replacement for the classical hash join.

For plans where one input is not sorted by the join variable, relational
engines use a hash join. On TPU, random-access hash probes are HBM-latency-
bound gathers; the idiomatic equivalent is *sort-based*: materialize the
build side once, sort it by the key (code order), and probe every stream
batch with a vectorized binary search (kernels sorted_search). The probe
then reuses the exact merge-join Build machinery — every probe row is a
length-1 left range joined against the matching build run. Emission runs
through the fused gather_emit kernel (probe gather + build gather +
NULL-extension of unmatched left_outer rows + secondary-key equality in
one dispatch) into pool-recycled buffers. Output preserves probe-side
order. See DESIGN.md §2 (hardware-adaptation table) and §2.3.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from repro.core import vecops
from repro.core.batch import NULL_ID, BatchPool, ColumnBatch, bucket_for
from repro.core.operators.base import BatchOperator
from repro.core.operators.sort import materialize
from repro.kernels import ops as KOPS


class LookupJoin(BatchOperator):
    emits_join_rows = True

    def __init__(
        self,
        probe: BatchOperator,
        build: BatchOperator,
        join_var: int,
        mode: str = "inner",
        pool: Optional[BatchPool] = None,
    ) -> None:
        assert mode in ("inner", "left_outer", "semi", "anti")
        self.probe = probe
        self.build = build
        self.v = join_var
        self.mode = mode
        self.pool = pool
        pv, bv = tuple(probe.var_ids()), tuple(build.var_ids())
        assert join_var in pv and join_var in bv
        self.secondary = tuple(x for x in pv if x in bv and x != join_var)
        # left_outer + secondary keys needs per-group survivor tracking —
        # the planner routes that case to MergeJoin (which implements it)
        assert not (mode == "left_outer" and self.secondary), (
            "LookupJoin left_outer with secondary join keys unsupported; use MergeJoin"
        )
        if mode in ("semi", "anti"):
            self._build_out: Tuple[int, ...] = ()
        else:
            self._build_out = tuple(x for x in bv if x not in pv)
        self._out_vars = pv + self._build_out
        self._built = False
        self._bcols: Optional[np.ndarray] = None
        self._bkeys: Optional[np.ndarray] = None
        self._bvars = bv
        # static gather_emit plan
        self._lsel = tuple(range(len(pv)))
        self._rsel = tuple(bv.index(x) for x in self._build_out)
        self._pairs = tuple((pv.index(sv), bv.index(sv)) for sv in self.secondary)
        # continuation of an oversized expansion
        self._pending: Optional[Tuple] = None
        super().__init__("LookupJoin", f"(?v{join_var}) mode={mode}")

    def var_ids(self) -> Tuple[int, ...]:
        return self._out_vars

    def sorted_by(self) -> Optional[int]:
        return self.probe.sorted_by()

    def children(self) -> List[BatchOperator]:
        return [self.probe, self.build]

    def _ensure_built(self) -> None:
        if self._built:
            return
        bvars, bcols = materialize(self.build)
        key = bcols[bvars.index(self.v)]
        order = np.argsort(key, kind="stable")
        self._bcols = bcols[:, order]
        self._bkeys = key[order]
        self._bvars = bvars
        self._built = True

    def _next(self) -> Optional[ColumnBatch]:
        self._ensure_built()
        cap = bucket_for(4096)
        while True:
            if self._pending is not None:
                out = self._emit_pending(cap)
                if out is not None:
                    if out.n_active:
                        return out
                    out.release()  # fully masked-out block: recycle
                continue
            pb = self.probe.next_batch()
            if pb is None:
                return None
            cb = pb.compact()
            if cb.n_rows == 0:
                cb.release()
                continue
            keys = cb.column(self.v)
            lo = vecops.sorted_search(self._bkeys, keys, "left")
            hi = vecops.sorted_search(self._bkeys, keys, "right")
            lens = (hi - lo).astype(np.int32)
            if self.mode == "semi":
                if self.secondary:
                    out = self._secondary_exists(cb, lo, lens, want_match=True)
                else:
                    m = np.zeros(cb.capacity, dtype=bool)
                    m[: cb.n_rows] = lens > 0
                    out = cb.with_mask(m)
                if out.n_active:
                    return out
                out.release()
                continue
            if self.mode == "anti" and not self.secondary:
                m = np.zeros(cb.capacity, dtype=bool)
                m[: cb.n_rows] = lens == 0
                out = cb.with_mask(m)
                if out.n_active:
                    return out
                out.release()
                continue
            if self.mode == "anti":
                out = self._secondary_exists(cb, lo, lens, want_match=False)
                if out.n_active:
                    return out
                out.release()
                continue
            # inner / left_outer: groups = (probe row i, build run lo[i:hi[i]))
            pstarts = np.arange(cb.n_rows, dtype=np.int32)
            plens = np.ones(cb.n_rows, dtype=np.int32)
            if self.mode == "left_outer":
                # unmatched probe rows emit one NULL-extended row: model them
                # as a run of length 1 against a virtual NULL build row
                eff_lens = np.maximum(lens, 1)
            else:
                keep = lens > 0
                pstarts, plens = pstarts[keep], plens[keep]
                lo, lens = lo[keep], lens[keep]
                eff_lens = lens
            if len(pstarts) == 0:
                cb.release()
                continue
            cum = vecops.group_output_offsets(plens, eff_lens)
            self._pending = (cb, pstarts, lo, lens, eff_lens, cum, 0)

    def _secondary_exists(self, cb, lo, lens, want_match: bool) -> ColumnBatch:
        """semi/anti with secondary keys: a probe row matches if any build
        row in its run agrees on all secondary keys — the fused equality
        mask of gather_emit, reduced per probe row."""
        n = cb.n_rows
        matched = np.zeros(n, dtype=bool)
        nz = np.nonzero(lens > 0)[0]
        if len(nz):
            pstarts = nz.astype(np.int32)
            plens = np.ones(len(nz), dtype=np.int32)
            cum = vecops.group_output_offsets(plens, lens[nz])
            total = int(cum[-1])
            li, ri = KOPS.join_expand(pstarts, plens, lo[nz], lens[nz], cum, 0, total)
            _, ok = KOPS.gather_emit(
                cb.columns, self._bcols, li, ri, (), (), self._pairs
            )
            if ok.any():
                np.logical_or.at(matched, li[ok], True)
        m = np.zeros(cb.capacity, dtype=bool)
        m[:n] = matched if want_match else ~matched
        return cb.with_mask(m)

    def _emit_pending(self, cap: int) -> Optional[ColumnBatch]:
        cb, pstarts, lo, lens, eff_lens, cum, emitted = self._pending
        total = int(cum[-1])
        count = min(cap, total - emitted)
        li, ri = KOPS.join_expand(
            pstarts, np.ones(len(pstarts), dtype=np.int32), lo, eff_lens, cum, emitted, count
        )
        base = emitted
        emitted += count
        done = emitted >= total
        self._pending = None if done else (
            cb, pstarts, lo, lens, eff_lens, cum, emitted
        )
        if self.mode == "left_outer":
            # rows from virtual NULL runs (unmatched probe rows): mark their
            # build index -1 so gather_emit NULL-extends them
            group_of = np.searchsorted(cum, base + np.arange(count), side="right") - 1
            ri = np.where(lens[group_of] == 0, np.int32(-1), ri)
        b = ColumnBatch.alloc(
            self._out_vars, bucket_for(max(count, 1)), self.pool, self.sorted_by()
        )
        _, mask = KOPS.gather_emit(
            cb.columns, self._bcols, li, ri,
            self._lsel, self._rsel, self._pairs, out=b.columns,
        )
        b.n_rows = count
        if count < b.capacity:
            b.columns[:, count:] = NULL_ID
        b.mask[:count] = mask
        if self.pool is not None:
            self.pool.bytes_copied += len(self._out_vars) * count * 4
        if done:
            cb.release()
        return b

    def _skip(self, var: int, target: int) -> None:
        if self._pending is not None:
            self._pending[0].release()
        self._pending = None
        self.probe.skip(var, target)

    def _reset(self) -> None:
        self.probe.reset()
        self.build.reset()
        if self._pending is not None:
            self._pending[0].release()
        self._pending = None
        self._built = False

    def _close(self) -> None:
        # early teardown mid-expansion: the pending probe batch still owns
        # pooled buffers
        if self._pending is not None:
            self._pending[0].release()
            self._pending = None
