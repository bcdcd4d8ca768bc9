"""Radix-partitioned vectorized hash join (DESIGN.md §11).

The general join for unsorted inputs: the build side is materialized once
and laid out by the hash_build kernel — rows bucketed by multiplicative-
hash partition id (the radix_partition kernel), key-sorted within each
partition — while the probe side streams through untouched, one vectorized
hash_probe dispatch per batch locating every probe key's contiguous match
run. Emission then reuses the exact merge-join Build machinery: every
probe row is a length-1 left range expanded against its run (join_expand)
and materialized through the fused gather_emit kernel into pool-recycled
buffers, so probe-side order is preserved and the probe side is never
sorted or materialized. This is what replaces the planner's double-PSort +
MergeJoin plan for unsorted inputs (§11 strategy table).

Join keys: every shared variable. One shared variable hashes its raw code
column (NULL_ID == -1 is an ordinary value that equals itself — the same
NULL semantics as MergeJoin and the row engine, pinned by the parity
sweeps). Multiple shared variables pack through vecops.pack_group_keys
with spans fixed from the build side (one sentinel slot per column so
out-of-range probe values can never falsely match) into an int64 split
into an (hi, lo) int32 pair for the kernels; if the span product overflows
62 bits, the join hashes the primary variable and verifies the rest
through gather_emit equality pairs.

Modes: inner, left_outer (OPTIONAL — incl. the LeftJoin *condition*, where
a probe row whose matches all fail the expression still emits NULL-
extended), semi, and anti on one machinery. An empty key tuple is the
degenerate constant-key join: inner == cross product, left_outer == the
NULL-extending cross that fixes disjoint OPTIONAL, anti == the
"remove everything iff the build has any row" shape that NOT EXISTS
needs when it shares no variables with the outer group.
"""

from __future__ import annotations

from time import perf_counter
from typing import List, Optional, Tuple

import numpy as np

from repro.core import vecops
from repro.core.adaptive import AdaptiveBatchSizer
from repro.core.batch import NULL_ID, BatchPool, ColumnBatch, bucket_for
from repro.core.expressions import eval_expr_mask
from repro.core.exprs import eval_program_mask
from repro.core.operators.base import BatchOperator
from repro.core.operators.sort import materialize
from repro.core.partition import (
    PartitionedRelation,
    next_pow2,
    partition_ids_multi,
    split_block,
)
from repro.kernels import ops as KOPS

# target rows per partition: partitions around this size keep the within-
# partition binary search shallow while the partition count stays small
# enough for the histogram kernel's one-hot reduction
_PART_TARGET = 4096
_MAX_PARTS = 1024

# grace mode (DESIGN.md §15): default top-level fan-out when the planner
# directed a grace build without sizing it, sub-fan-out per recursive
# re-partition of a skewed bucket, and the recursion depth cap (4 hash
# levels with distinct multipliers; a bucket still over budget at level 3
# is one hot key and builds resident regardless)
_GRACE_DEFAULT_PARTS = 32
_GRACE_SUB_PARTS = 8
_GRACE_MAX_LEVEL = 3
_GRACE_PROBE_CHUNK = 4096


def _n_parts_for(n_build: int) -> int:
    p = 1
    while p * _PART_TARGET < n_build and p < _MAX_PARTS:
        p *= 2
    return p


class HashJoin(BatchOperator):
    emits_join_rows = True

    def __init__(
        self,
        probe: BatchOperator,
        build: BatchOperator,
        keys: Tuple[int, ...],
        mode: str = "inner",
        post_filter=None,  # LeftJoin condition (OPTIONAL {...} FILTER)
        dictionary=None,
        sizer: Optional[AdaptiveBatchSizer] = None,
        pool: Optional[BatchPool] = None,
        post_program=None,  # compiled ExprProgram for post_filter (planner)
        backend: Optional[str] = None,  # kernel backend override (tests)
        n_parts: Optional[int] = None,
        memory_budget: Optional[int] = None,  # bytes; None = resident only
        spill_dir: Optional[str] = None,
        grace: Optional[bool] = None,  # True = planner-directed grace build
        grace_parts: int = 0,  # planner-chosen top-level fan-out (0 = auto)
    ) -> None:
        assert mode in ("inner", "left_outer", "semi", "anti")
        self.probe = probe
        self.build = build
        self.keys = tuple(keys)
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
        self.pool = pool
        self.backend = backend
        self._n_parts_cfg = n_parts
        self.memory_budget = memory_budget
        self.spill_dir = spill_dir
        self.grace = grace
        self.grace_parts = grace_parts

        pv, bv = tuple(probe.var_ids()), tuple(build.var_ids())
        self._pv, self._bv = pv, bv
        shared = tuple(x for x in pv if x in bv)
        assert all(k in shared for k in self.keys), (self.keys, shared)
        # shared vars outside the hash key are verified per emitted row via
        # the fused gather_emit equality pairs (like MergeJoin secondaries)
        self._extra_shared = tuple(x for x in shared if x not in self.keys)
        if mode in ("semi", "anti"):
            self._build_out: Tuple[int, ...] = ()
        else:
            self._build_out = tuple(x for x in bv if x not in pv)
        self._out_vars = pv + self._build_out
        self._rsel = tuple(bv.index(x) for x in self._build_out)

        # build-side state (filled by _ensure_built)
        self._built = False
        self._probe_cache: dict = {}
        self._bcols: Optional[np.ndarray] = None  # partition-grouped layout
        self._n_build = 0
        self._n_parts = 1
        self._part_starts: Optional[np.ndarray] = None
        self._spid: Optional[np.ndarray] = None
        self._skh: Optional[np.ndarray] = None
        self._skl: Optional[np.ndarray] = None
        self._spans: Optional[List[int]] = None  # fixed multi-key pack spans
        self._hash_vars: Tuple[int, ...] = self.keys  # may shrink on overflow
        self._pair_vars: Tuple[int, ...] = self._extra_shared

        # grace-mode state (DESIGN.md §15): both sides fanned out once by
        # partition_ids_multi, then joined one partition at a time with the
        # resident radix machinery above
        self._grace_active = False
        self._build_rel: Optional[PartitionedRelation] = None
        self._probe_rel: Optional[PartitionedRelation] = None
        self._probe_partitioned = False
        # work stack of (build_block, probe_block, level) from recursive
        # re-partitioning of skewed buckets; consumed before fresh take()s
        self._grace_stack: List[Tuple[np.ndarray, np.ndarray, int]] = []
        self._next_gp = 0
        self._gp_cols: Optional[np.ndarray] = None  # current probe block
        self._gp_off = 0

        # probe-side continuation state
        self._pending: Optional[Tuple] = None
        # (cb, matched) for left_outer runs that need per-row match tracking
        self._track: Optional[Tuple] = None
        self._leftovers: List[np.ndarray] = []  # (n_pv, n) unmatched rows
        # skip() floor: a parent may gallop us past `target` while pending
        # expansions still hold rows >= target — those must survive, so the
        # floor masks emitted rows below it instead of dropping the batch
        self._skip_floor: Optional[Tuple[int, int]] = None
        super().__init__("HashJoin", f"({','.join(f'?v{k}' for k in keys)}) mode={mode}")

    # -- metadata ---------------------------------------------------------------

    def var_ids(self) -> Tuple[int, ...]:
        return self._out_vars

    def sorted_by(self) -> Optional[int]:
        # probe order is preserved: expansions walk probe rows in order and
        # plain left_outer NULL rows are emitted in place. Tracked
        # left_outer (join condition / pair fallback) queues its NULL rows
        # after the batch's expansions, breaking the interleave. Grace mode
        # re-orders the probe side by partition, so it preserves nothing.
        if self._grace_active or self.grace:
            return None
        if self.mode == "left_outer" and self._needs_tracking():
            return None
        return self.probe.sorted_by()

    def children(self) -> List[BatchOperator]:
        return [self.probe, self.build]

    def _needs_tracking(self) -> bool:
        return self.mode == "left_outer" and (
            self.post_filter is not None or bool(self._pair_vars)
        )

    # -- build phase -------------------------------------------------------------

    def _ensure_built(self) -> None:
        if self._built:
            return
        t0 = perf_counter()
        if self.grace and self.keys:
            # planner-directed grace build: stream the build child straight
            # into the partitioned relation — it is never fully resident
            self._grace_build_stream()
            self._built = True
            self.stats.extra["hash_build_ms"] = round(
                (perf_counter() - t0) * 1e3, 3)
            return
        bvars, bcols = materialize(self.build)
        self._bv = bvars
        self._rsel = tuple(bvars.index(x) for x in self._build_out)
        n = int(bcols.shape[1])
        self._n_build = n
        if not self.keys:
            self._bcols = bcols
            self._built = True
            self.stats.extra["hash_build_rows"] = n
            self.stats.extra["hash_build_ms"] = round(
                (perf_counter() - t0) * 1e3, 3)
            return
        if (
            self.memory_budget is not None
            and bcols.nbytes > self.memory_budget
            and self.probe.sorted_by() is None
        ):
            # runtime resident->grace switch: the planner sized this build
            # as resident but actuals blew the budget. Only taken when no
            # ancestor relies on probe order (unsorted probe), since grace
            # re-orders emission by partition.
            self._grace_switch_from_block(bcols)
            self._built = True
            self.stats.extra["hash_build_rows"] = n
            self.stats.extra["hash_build_ms"] = round(
                (perf_counter() - t0) * 1e3, 3)
            return
        self._build_resident(bcols)
        self._built = True
        self.stats.extra["hash_build_rows"] = n
        self.stats.extra["hash_partitions"] = self._n_parts
        self.stats.extra["hash_build_ms"] = round((perf_counter() - t0) * 1e3, 3)

    def _build_resident(self, bcols: np.ndarray) -> None:
        """Radix-build one in-memory block (full build side, or one grace
        partition at a time). Resets the span/pair layout per block: a
        multi-key span overflow in one grace partition must not leak its
        primary-only fallback into the next."""
        n = int(bcols.shape[1])
        self._n_build = n
        kcols = bcols[[self._bv.index(k) for k in self.keys]]
        self._spans = None
        self._hash_vars = self.keys
        self._pair_vars = self._extra_shared
        if len(self.keys) > 1:
            # one sentinel slot per column (max+3) so clamped out-of-range
            # probe values can never collide with a real build key
            spans = [int(c.max(initial=-1)) + 3 for c in kcols]
            packed = vecops.pack_group_keys(kcols, spans=spans)
            if packed is None:
                # span overflow: hash the primary key, verify the rest via
                # gather_emit equality pairs
                self._hash_vars = self.keys[:1]
                self._pair_vars = self.keys[1:] + self._extra_shared
                bh, bl = None, np.ascontiguousarray(kcols[0])
            else:
                self._spans = spans
                bh = (packed >> 31).astype(np.int32)
                bl = (packed & 0x7FFFFFFF).astype(np.int32)
        else:
            bh, bl = None, np.ascontiguousarray(kcols[0])
        self._n_parts = self._n_parts_cfg or _n_parts_for(n)
        order, part_starts = KOPS.hash_build(
            bh, bl, self._n_parts, backend=self.backend
        )
        self._bcols = bcols[:, order]
        self._part_starts = part_starts
        self._spid = np.repeat(
            np.arange(self._n_parts, dtype=np.int32), np.diff(part_starts)
        )
        self._skh = None if bh is None else bh[order]
        self._skl = bl[order]
        self._probe_cache = {}  # per-build composite cache (kernels.ops)

    # -- grace phase (DESIGN.md §15) ---------------------------------------------

    def _grace_fanout(self) -> int:
        g = self.grace_parts or _GRACE_DEFAULT_PARTS
        return max(2, next_pow2(g))

    def _init_rels(self, n_parts: int) -> None:
        half = None if self.memory_budget is None else max(
            self.memory_budget // 2, 1
        )
        self._build_rel = PartitionedRelation(
            len(self._bv), n_parts, self.spill_dir, half, self.pool
        )
        self._probe_rel = PartitionedRelation(
            len(self._pv), n_parts, self.spill_dir, half, self.pool
        )
        self._next_gp = 0
        self._grace_stack = []
        self._gp_cols = None
        self._gp_off = 0
        self._probe_partitioned = False
        self.stats.extra["grace_partitions"] = n_parts
        self.stats.extra.setdefault("repartitions", 0)

    def _grace_build_stream(self) -> None:
        g = self._grace_fanout()
        self._init_rels(g)
        total = 0
        while True:
            b = self.build.next_batch()
            if b is None:
                break
            cb = b.compact()
            n = cb.n_rows
            if n == 0:
                cb.release()
                continue
            pids = partition_ids_multi(
                [cb.column(k) for k in self.keys], g
            )
            cols = np.stack([cb.column(v) for v in self._bv])
            self._build_rel.append(cols, pids)
            total += n
            cb.release()
        self._grace_active = True
        self.stats.extra["hash_build_rows"] = total
        self._refresh_grace_stats()

    def _grace_switch_from_block(self, bcols: np.ndarray) -> None:
        # fan-out sized so an average partition fits in half the budget
        # (the other half is headroom for the probe partitions)
        g = min(
            256,
            max(2, next_pow2(
                -(-int(bcols.nbytes) // max(self.memory_budget // 2, 1))
            )),
        )
        self._init_rels(g)
        pids = partition_ids_multi(
            [bcols[self._bv.index(k)] for k in self.keys], g
        )
        self._build_rel.append(bcols, pids)
        self._grace_active = True
        self.stats.extra["adaptive_switches"] = 1
        self.stats.detail += " grace"
        self._refresh_grace_stats()

    def _grace_partition_probe(self) -> None:
        g = self._build_rel.n_parts
        while True:
            b = self.probe.next_batch()
            if b is None:
                break
            cb = b.compact()
            n = cb.n_rows
            if n == 0:
                cb.release()
                continue
            pids = partition_ids_multi(
                [cb.column(k) for k in self.keys], g
            )
            cols = np.stack([cb.column(v) for v in self._pv])
            self._probe_rel.append(cols, pids)
            cb.release()
        self._probe_partitioned = True
        self._refresh_grace_stats()

    def _refresh_grace_stats(self) -> None:
        sb = sf = 0
        for rel in (self._build_rel, self._probe_rel):
            if rel is not None:
                sb += rel.spill_bytes
                sf += rel.spill_files
        self.stats.extra["spill_bytes"] = sb
        self.stats.extra["spill_files"] = sf

    def _grace_next_probe(self) -> Optional[ColumnBatch]:
        """Probe-side source while grace is active: chunks of the current
        partition's probe block, advancing partitions in between. Returns
        None when exhausted OR when leftovers were queued (the caller's
        loop flushes them before asking again)."""
        if not self._probe_partitioned:
            self._grace_partition_probe()
        while True:
            if self._gp_cols is not None:
                if self._gp_off < self._gp_cols.shape[1]:
                    j = min(
                        self._gp_off + _GRACE_PROBE_CHUNK,
                        self._gp_cols.shape[1],
                    )
                    chunk = self._gp_cols[:, self._gp_off : j]
                    self._gp_off = j
                    return ColumnBatch.from_columns(
                        self._pv,
                        [chunk[i] for i in range(chunk.shape[0])],
                        None,
                        pool=self.pool,
                    )
                self._gp_cols = None
            if self._leftovers:
                return None  # flush NULL-extension leftovers first
            if not self._grace_advance():
                return None

    def _grace_advance(self) -> bool:
        """Move to the next joinable (build, probe) partition pair. Skewed
        buckets over budget re-partition recursively with a fresh hash
        multiplier per level instead of building an over-budget table."""
        while True:
            if self._grace_stack:
                bblock, pblock, level = self._grace_stack.pop()
            elif self._next_gp < self._build_rel.n_parts:
                g = self._next_gp
                self._next_gp += 1
                bblock = self._build_rel.take(g)
                pblock = self._probe_rel.take(g)
                level = 0
                self._refresh_grace_stats()
            else:
                return False
            if pblock.shape[1] == 0:
                continue
            if bblock.shape[1] == 0:
                # probe-only partition: inner/semi emit nothing; anti and
                # left_outer NULL-extend every probe row via the leftovers
                # path (build_out is empty for anti, so it emits as-is)
                if self.mode in ("anti", "left_outer"):
                    self._leftovers.append(np.ascontiguousarray(pblock))
                    return True
                continue
            if (
                self.memory_budget is not None
                and bblock.nbytes > self.memory_budget
                and level < _GRACE_MAX_LEVEL
                and bblock.shape[1] > 1
                and not self._all_keys_equal(bblock)
            ):
                self._grace_repartition(bblock, pblock, level)
                continue
            self._build_resident(bblock)
            self._gp_cols = pblock
            self._gp_off = 0
            return True

    def _all_keys_equal(self, bblock: np.ndarray) -> bool:
        for k in self.keys:
            c = bblock[self._bv.index(k)]
            if c.shape[0] and not (c == c[0]).all():
                return False
        return True

    def _grace_repartition(
        self, bblock: np.ndarray, pblock: np.ndarray, level: int
    ) -> None:
        g2 = _GRACE_SUB_PARTS
        b_pids = partition_ids_multi(
            [bblock[self._bv.index(k)] for k in self.keys], g2, level + 1
        )
        p_pids = partition_ids_multi(
            [pblock[self._pv.index(k)] for k in self.keys], g2, level + 1
        )
        bsubs = dict(split_block(bblock, b_pids, g2))
        psubs = dict(split_block(pblock, p_pids, g2))
        empty_b = np.empty((bblock.shape[0], 0), dtype=np.int32)
        for p, psub in psubs.items():
            self._grace_stack.append(
                (bsubs.get(p, empty_b), psub, level + 1)
            )
        self.stats.extra["repartitions"] = (
            self.stats.extra.get("repartitions", 0) + 1
        )

    def sip_keys(self, var: int) -> np.ndarray:
        """Build-side key column for a SipFilter export (DESIGN.md §12).
        Runs the build phase if needed — safe, because _next() always
        builds before the first probe batch is pulled, so forcing it from
        a probe-side leaf's first batch only moves the same work earlier.
        The partition-grouped reorder doesn't matter: the bloom filter is
        order-insensitive."""
        self._ensure_built()
        self.stats.extra["sip_exports"] = (
            self.stats.extra.get("sip_exports", 0) + 1
        )
        if self._grace_active:
            # partitioned build: concatenate the key column across
            # partitions (load without freeing — the grace drain still
            # needs them). SIP gating means small builds, so this is rare.
            j = self._bv.index(var)
            parts = [
                self._build_rel.load(p)[j]
                for p in range(self._build_rel.n_parts)
            ]
            return np.ascontiguousarray(np.concatenate(parts))
        return np.ascontiguousarray(
            self._bcols[self._bv.index(var), : self._n_build]
        )

    # -- probe phase -------------------------------------------------------------

    def _probe_keys(self, cb: ColumnBatch) -> Tuple[Optional[np.ndarray], np.ndarray]:
        kcols = [cb.column(v) for v in self._hash_vars]
        if self._spans is not None:
            packed = vecops.pack_group_keys(np.stack(kcols), spans=self._spans)
            return (
                (packed >> 31).astype(np.int32),
                (packed & 0x7FFFFFFF).astype(np.int32),
            )
        return None, np.ascontiguousarray(kcols[0], dtype=np.int32)

    def _run_bounds(self, cb: ColumnBatch) -> Tuple[np.ndarray, np.ndarray]:
        """(lo, len) of each probe row's build match run."""
        n = cb.n_rows
        if not self.keys:  # constant-key degenerate join: match everything
            return (
                np.zeros(n, dtype=np.int32),
                np.full(n, self._n_build, dtype=np.int32),
            )
        qh, ql = self._probe_keys(cb)
        t0 = perf_counter()
        lo, hi = KOPS.hash_probe(
            self._spid, self._skh, self._skl, qh, ql,
            self._part_starts, self._n_parts, backend=self.backend,
            cache=self._probe_cache,
        )
        self.stats.extra["hash_probe_ms"] = round(
            self.stats.extra.get("hash_probe_ms", 0.0)
            + (perf_counter() - t0) * 1e3, 3)
        self.stats.extra["hash_probe_rows"] = (
            self.stats.extra.get("hash_probe_rows", 0) + n)
        return lo, (hi - lo).astype(np.int32)

    def _pairs_for(self, cb: ColumnBatch) -> Tuple[Tuple[int, int], ...]:
        return tuple(
            (cb.col_index(v), self._bv.index(v)) for v in self._pair_vars
        )

    def _next(self) -> Optional[ColumnBatch]:
        self._ensure_built()
        cap = bucket_for(self.sizer.on_next())
        while True:
            if self._pending is not None:
                out = self._emit_pending(cap)
                if self._pending is None and self._track is not None:
                    self._finalize_tracked()
                if out is not None and out.n_active:
                    return out
                if out is not None:
                    out.release()
                continue
            if self._leftovers:
                return self._emit_leftovers(cap)
            if self._grace_active:
                pb = self._grace_next_probe()
                if pb is None:
                    if self._leftovers:
                        continue  # loop top flushes them
                    return None
            else:
                pb = self.probe.next_batch()
                if pb is None:
                    return None
            cb = pb.compact()
            if cb.n_rows == 0:
                cb.release()
                continue
            out = self._probe_batch(cb)
            if out is not None:
                if out.n_active:
                    return out
                out.release()

    def _probe_batch(self, cb: ColumnBatch) -> Optional[ColumnBatch]:
        """Consume one compacted probe batch: either a masked filter result
        (semi/anti), a queued pending expansion (inner/left_outer), or
        queued NULL-extension leftovers."""
        n = cb.n_rows
        lo, lens = self._run_bounds(cb)
        pairs = self._pairs_for(cb)

        if self.mode in ("semi", "anti"):
            if pairs:
                return self._pairwise_exists(
                    cb, lo, lens, pairs, want=self.mode == "semi"
                )
            m = np.zeros(cb.capacity, dtype=bool)
            m[:n] = (lens > 0) if self.mode == "semi" else (lens == 0)
            return cb.with_mask(m)

        if self.mode == "inner" or self._needs_tracking():
            keep = np.nonzero(lens > 0)[0].astype(np.int32)
            if self._needs_tracking():
                matched = np.zeros(n, dtype=bool)
                self._track = (cb, matched)
                if len(keep) == 0:
                    self._finalize_tracked()
                    return None
            elif len(keep) == 0:
                cb.release()
                return None
            plens = np.ones(len(keep), dtype=np.int32)
            cum = vecops.group_output_offsets(plens, lens[keep])
            self._pending = (cb, keep, lo[keep], lens[keep], lens[keep],
                             cum, 0, pairs)
            return None

        # plain left_outer: unmatched probe rows become a run of length 1
        # against a virtual NULL build row (ri == -1 in gather_emit)
        eff = np.maximum(lens, 1)
        pstarts = np.arange(n, dtype=np.int32)
        cum = vecops.group_output_offsets(np.ones(n, np.int32), eff)
        self._pending = (cb, pstarts, lo, lens, eff, cum, 0, pairs)
        return None

    _EXISTS_CHUNK = 1 << 16

    def _pairwise_exists(self, cb, lo, lens, pairs, want: bool) -> ColumnBatch:
        """semi/anti with pair-verified keys: a probe row matches iff any
        build row in its run agrees on every pair column. The expansion is
        verified in bounded chunks — a skewed key's run cross product must
        not materialize at once (cf. _emit_pending's cap)."""
        n = cb.n_rows
        matched = np.zeros(n, dtype=bool)
        nz = np.nonzero(lens > 0)[0]
        if len(nz):
            pstarts = nz.astype(np.int32)
            plens = np.ones(len(nz), dtype=np.int32)
            cum = vecops.group_output_offsets(plens, lens[nz])
            total = int(cum[-1])
            done = 0
            while done < total:
                count = min(self._EXISTS_CHUNK, total - done)
                li, ri = KOPS.join_expand(
                    pstarts, plens, lo[nz], lens[nz], cum, done, count
                )
                _, ok = KOPS.gather_emit(
                    cb.columns, self._bcols, li, ri, (), (), pairs
                )
                if ok.any():
                    np.logical_or.at(matched, li[ok], True)
                done += count
        m = np.zeros(cb.capacity, dtype=bool)
        m[:n] = matched if want else ~matched
        return cb.with_mask(m)

    # -- emission ----------------------------------------------------------------

    def _emit_pending(self, cap: int) -> Optional[ColumnBatch]:
        cb, pstarts, lo, lens, eff, cum, emitted, pairs = self._pending
        total = int(cum[-1])
        count = min(cap, total - emitted)
        li, ri = KOPS.join_expand(
            pstarts, np.ones(len(pstarts), dtype=np.int32), lo, eff,
            cum, emitted, count,
        )
        base = emitted
        emitted += count
        done = emitted >= total
        self._pending = None if done else (
            cb, pstarts, lo, lens, eff, cum, emitted, pairs
        )
        if self.mode == "left_outer" and self._track is None:
            # virtual NULL runs: unmatched probe rows gather build index -1
            group_of = np.searchsorted(
                cum, base + np.arange(count), side="right") - 1
            ri = np.where(lens[group_of] == 0, np.int32(-1), ri)

        lsel = tuple(cb.col_index(v) for v in self._pv)
        b = ColumnBatch.alloc(
            self._out_vars, bucket_for(max(count, 1)), self.pool,
            self.sorted_by(),
        )
        _, mask = KOPS.gather_emit(
            cb.columns, self._bcols, li, ri, lsel, self._rsel, pairs,
            out=b.columns,
        )
        b.n_rows = count
        if count < b.capacity:
            b.columns[:, count:] = NULL_ID
        b.mask[:count] = mask
        if self.pool is not None:
            self.pool.bytes_copied += len(self._out_vars) * count * 4
        if self.post_filter is not None:
            if self.post_program is not None:
                b = b.with_mask(
                    eval_program_mask(self.post_program, b, self.dictionary)
                )
            else:
                b = b.with_mask(
                    eval_expr_mask(self.post_filter, b, self.dictionary)
                )
        if self._track is not None:
            surv = b.mask[:count]
            if surv.any():
                self._track[1][li[surv]] = True
        if self._skip_floor is not None:
            # applied AFTER match tracking: a skipped row still counts as
            # matched for left_outer bookkeeping, it just isn't re-emitted
            fv, ft = self._skip_floor
            floor = np.ones(b.capacity, dtype=bool)
            floor[:count] = cb.columns[cb.col_index(fv), li] >= ft
            b = b.with_mask(floor)
        if done and self._track is None:
            cb.release()
        return b

    def _finalize_tracked(self) -> None:
        cb, matched = self._track
        self._track = None
        um = np.nonzero(~matched)[0].astype(np.int32)
        if len(um):
            idx = [cb.col_index(v) for v in self._pv]
            self._leftovers.append(np.asarray(cb.columns[idx][:, um]))
        cb.release()

    def _emit_leftovers(self, cap: int) -> ColumnBatch:
        rows = self._leftovers.pop(0)
        if self._skip_floor is not None:
            fv, ft = self._skip_floor
            rows = rows[:, rows[self._pv.index(fv)] >= ft]
        n = rows.shape[1]
        if n > cap:
            self._leftovers.insert(0, rows[:, cap:])
            rows = rows[:, :cap]
            n = cap
        out_cols = [rows[i] for i in range(rows.shape[0])]
        for _ in self._build_out:
            out_cols.append(np.full(n, NULL_ID, dtype=np.int32))
        return ColumnBatch.from_columns(
            self._out_vars, out_cols, None, pool=self.pool
        )

    # -- control ----------------------------------------------------------------

    def _drop_pending(self) -> None:
        if self._pending is not None:
            if self._track is None:
                self._pending[0].release()
            self._pending = None
        if self._track is not None:
            self._track[0].release()
            self._track = None
        self._leftovers.clear()

    def _skip(self, var: int, target: int) -> None:
        # pending expansions and leftovers may still hold rows >= target:
        # narrow them with a floor mask at emission instead of dropping
        if self._skip_floor is not None and self._skip_floor[0] == var:
            target = max(target, self._skip_floor[1])
        self._skip_floor = (var, target)
        self.probe.skip(var, target)

    def _close(self) -> None:
        # grace spill teardown — reached via executor finally even when a
        # mid-query exception aborts the drain (ISSUE-9 leak fix)
        for rel in (self._build_rel, self._probe_rel):
            if rel is not None:
                rel.close()

    def _reset(self) -> None:
        self._drop_pending()
        self._skip_floor = None
        self.probe.reset()
        self.build.reset()
        self._built = False
        self._probe_cache = {}
        self._bcols = None
        self._part_starts = None
        self._spid = self._skh = self._skl = None
        self._spans = None
        self._hash_vars = self.keys
        self._pair_vars = self._extra_shared
        self._close()
        self._grace_active = False
        self._build_rel = self._probe_rel = None
        self._probe_partitioned = False
        self._grace_stack = []
        self._next_gp = 0
        self._gp_cols = None
        self._gp_off = 0
