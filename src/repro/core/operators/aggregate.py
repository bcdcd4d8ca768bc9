"""Vectorized grouping engine (paper §3.3, DESIGN.md §10).

StreamingGroupBy handles the paper's optimized case: a single group variable
with input sorted by it. Standard aggregates (count/sum/min/max/avg) are
associative, so every batch reduces to per-run partials with ONE
``kernels.ops.segment_reduce`` dispatch per required statistic (numpy
oracle / jnp ref / Pallas segmented scan) and a single scalar carry for the
run spanning the batch boundary — no Python-level per-run loops. DISTINCT
aggregates sort each batch by (group, code) and dedup through the
``frontier_dedup`` kernel (adjacent-unique over sorted pairs); only the
boundary run keeps an explicit code set, merged by sorted union.

Semantics (shared with the legacy row engine and pinned by
tests/test_aggregate.py):

  * COUNT counts *bound* terms (numeric or not); every other aggregate
    restricts to numeric terms via the dictionary side-array;
  * DISTINCT dedups bound codes before the aggregate function is applied —
    ``SUM(DISTINCT ?x)`` sums the distinct values, it is not a count;
  * MIN/MAX/AVG over an empty (or all-unbound / all-non-numeric) group
    leave the output variable unbound instead of encoding NaN.

Backend note: numpy is the host data plane and the float64 oracle; the
jnp/Pallas segmented scans accumulate in float32, so their SUM/AVG partials
are exact only for f32-representable magnitudes (integer sums below 2^24 —
the same caveat as the expression VM, DESIGN.md §9.5). COUNT(DISTINCT *)
is rejected at parse time rather than silently approximated (it would need
whole-solution dedup, not a per-column code set).

SortGroupBy is the general fallback (multi-var or unsorted input): it
drains only the needed columns from pooled batches, sorts ONCE by a packed
int64 composite key, assigns dense group ids, and streams the sorted runs
through StreamingGroupBy — sort-based grouping, the TPU-idiomatic
replacement for vectorized hash grouping (DESIGN.md §2).

Weighted counting (DESIGN.md §17) runs on these same operators. Where
the input carries row weights (``batch.WEIGHT_VAR`` pairs) or the group
emits one (a fold), grouping sums each group's int64 weights exactly on
the host instead of counting rows: COUNT(*) is that sum, and a fold
emits it as the weight pair of the merged row. All aggregates of such a
group are COUNT(*) (the planner's rule).

StreamingDistinct implements DISTINCT-via-skip() for sorted inputs: after
seeing key k it *skips* the child to k+1, scrolling over duplicates in
storage (paper: 'highly efficient for queries with many duplicates').
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import vecops
from repro.core.algebra import AggSpec
from repro.core.batch import (
    MAX_BATCH,
    NULL_ID,
    BatchPool,
    ColumnBatch,
    is_weight_var,
    row_weights,
    weight_pair,
)
from repro.core.dictionary import Dictionary
from repro.core.operators.base import BatchOperator
from repro.core.operators.sort import MaterializedSource, materialize
from repro.core.partition import PartitionedRelation, partition_ids_multi
from repro.kernels import ops

_EMPTY_I32 = np.zeros(0, dtype=np.int32)

# per-run statistics each (func, distinct) aggregate consumes; 'cnt' is the
# run length, 'bnd'/'nn' count bound / numeric rows, 'sum'/'min'/'max' fold
# numeric values, and the d-prefixed stats fold over the per-run distinct
# bound codes (DESIGN.md §10)
_NEEDS: Dict[Tuple[str, bool], Tuple[str, ...]] = {
    ("count*", False): ("cnt",),
    ("count*", True): ("cnt",),  # hand-built plans only: parser rejects it
    ("count", False): ("bnd",),
    ("count", True): ("dbnd",),
    ("sum", False): ("sum",),
    ("sum", True): ("dsum",),
    ("min", False): ("min", "nn"),
    ("min", True): ("min", "nn"),  # distinct never changes an extremum
    ("max", False): ("max", "nn"),
    ("max", True): ("max", "nn"),
    ("avg", False): ("sum", "nn"),
    ("avg", True): ("dsum", "dnn"),
}

_DISTINCT_STATS = ("dbnd", "dnn", "dsum")
_SCALAR_INIT = {
    "cnt": 0.0, "bnd": 0.0, "nn": 0.0, "sum": 0.0,
    "min": np.inf, "max": -np.inf,
}


def _agg_needs(a: AggSpec) -> Tuple[str, ...]:
    func = "count*" if a.var is None else a.func
    return _NEEDS[(func, a.distinct)]


@dataclasses.dataclass
class _Carry:
    """Scalar partials for the group run spanning the batch boundary.

    Associative stats merge as scalars; the DISTINCT stats cannot (codes in
    the next batch may repeat earlier ones), so for DISTINCT count/sum/avg
    the carry collects each batch's sorted-unique bound-code slice and
    dedups ONCE when the run provably closes — appending chunks keeps a
    group spanning B batches O(total codes), not O(B * total)."""

    key: Optional[int] = None
    stats: Optional[List[Dict[str, float]]] = None  # per-agg scalar partials
    dcodes: Optional[Dict[int, List[np.ndarray]]] = None  # per-agg code chunks


def _group_name(weight: Sequence[int]) -> str:
    return "Fold" if weight else "Group"


class StreamingGroupBy(BatchOperator):
    """GROUP BY <one var> with aggregates over input sorted by that var.
    group_var None => global aggregation (single group). ``weight``: the
    fold's output weight pair (DESIGN.md §17)."""

    def __init__(
        self,
        child: BatchOperator,
        group_var: Optional[int],
        aggs: Sequence[AggSpec],
        dictionary: Dictionary,
        batch_size: int = MAX_BATCH,
        pool: Optional[BatchPool] = None,
        backend: Optional[str] = None,
        weight: Sequence[int] = (),
    ):
        if group_var is not None:
            assert child.sorted_by() == group_var, "input must be sorted by group var"
        self.child = child
        self.g = group_var
        self.aggs = list(aggs)
        self.dictionary = dictionary
        self.batch_size = batch_size
        self.pool = pool
        self.backend = backend
        self.weight = tuple(weight)
        self.weighted = bool(self.weight) or any(
            is_weight_var(v) for v in child.var_ids()
        )
        assert not self.weighted or all(a.var is None for a in self.aggs), (
            "a weighted group computes COUNT(*) alone"
        )
        self._out_w: List[np.ndarray] = []  # weighted: int64 sum per group
        self._needs = [_agg_needs(a) for a in self.aggs]
        self._dset_aggs = tuple(
            ai for ai, need in enumerate(self._needs)
            if any(s in _DISTINCT_STATS for s in need)
        )
        self._out_keys: List[np.ndarray] = []
        self._out_vals: List[List[np.ndarray]] = [[] for _ in self.aggs]
        self._carry = _Carry()
        self._enc_keys: Optional[np.ndarray] = None
        self._enc_cols: List[np.ndarray] = []
        self._emitted = 0
        self._drained = False
        self._sr_calls = 0
        self._sr_ms = 0.0
        self._dd_calls = 0
        self._dd_ms = 0.0
        self._runs = 0
        super().__init__(
            _group_name(self.weight),
            f"by=?v{group_var} " + ",".join(f"{a.func}->?v{a.out}" for a in aggs),
        )

    def var_ids(self) -> Tuple[int, ...]:
        base = (self.g,) if self.g is not None else ()
        return base + tuple(a.out for a in self.aggs) + self.weight

    def sorted_by(self) -> Optional[int]:
        return self.g

    def children(self) -> List[BatchOperator]:
        return [self.child]

    # -- kernel dispatch ---------------------------------------------------------

    def _reduce(self, keys: np.ndarray, values: Optional[np.ndarray],
                func: str, seg=None) -> np.ndarray:
        t0 = time.perf_counter()
        _, out = ops.segment_reduce(
            keys, values, func, backend=self.backend, seg=seg
        )
        self._sr_ms += time.perf_counter() - t0
        self._sr_calls += 1
        return np.asarray(out, dtype=np.float64)

    # -- aggregation -------------------------------------------------------------

    def _consume_all(self) -> None:
        rows = 0
        while True:
            b = self.child.next_batch()
            if b is None:
                break
            cb = b.compact()
            if cb.n_rows == 0:
                cb.release()
                continue
            keys = (
                cb.column(self.g)
                if self.g is not None
                else np.zeros(cb.n_rows, dtype=np.int32)
            )
            rows += cb.n_rows
            if self.weighted:
                self._consume_weighted(keys, cb)
            else:
                self._consume_batch(keys, cb)
            cb.release()  # per-run partials copied into outputs / carry
        if self.weighted:
            if self.g is None and not self._out_keys and self.aggs:
                # a global COUNT(*) over empty input is one row of 0; a
                # fold of an empty input is no row
                self._out_keys.append(np.zeros(1, dtype=np.int32))
                self._out_w.append(np.zeros(1, dtype=np.int64))
            self.stats.extra["fold_rows_in"] = rows
            self.stats.extra["fold_rows_out"] = sum(map(len, self._out_keys))
            self._drained = True
            return
        self._close_carry()
        if self.g is None and not self._out_keys:
            # global aggregate over empty input still yields one row
            # (COUNT = 0, SUM = 0; MIN/MAX/AVG stay unbound)
            self._carry = self._open_carry(0)
            self._close_carry()
        self.stats.extra["group_runs"] = self._runs
        self.stats.extra["segment_reduce"] = self._sr_calls
        self.stats.extra["segment_reduce_ms"] = round(self._sr_ms * 1e3, 3)
        if self._dd_calls:
            self.stats.extra["distinct_dedup"] = self._dd_calls
            self.stats.extra["distinct_dedup_ms"] = round(self._dd_ms * 1e3, 3)
        self._drained = True

    def _batch_stats(self, keys: np.ndarray, cb: ColumnBatch, n_runs: int,
                     seg=None):
        """Per-run partial arrays for every aggregate, one segment_reduce
        dispatch per distinct (var, stat) pair — all sharing the batch's
        precomputed ``seg`` boundaries (the within-run sort of the distinct
        path permutes rows only inside runs, so the boundaries coincide).
        Returns (stats, dinfo): stats[ai][stat] is a (n_runs,) float64
        array; dinfo[ai] is the (sorted_codes, keep_mask) pair used to
        slice the unique bound codes of a boundary run out of the sorted
        batch."""
        col_cache: Dict[int, Dict[str, np.ndarray]] = {}
        dsort_cache: Dict[int, Tuple[np.ndarray, ...]] = {}
        job_cache: Dict[Tuple[int, str], np.ndarray] = {}

        def cols_of(var: int) -> Dict[str, np.ndarray]:
            c = col_cache.get(var)
            if c is None:
                codes = cb.column(var)
                vals = self.dictionary.numeric_of(codes)
                c = {"codes": codes, "vals": vals, "valid": ~np.isnan(vals)}
                col_cache[var] = c
            return c

        def dsort_of(var: int) -> Tuple[np.ndarray, ...]:
            d = dsort_cache.get(var)
            if d is None:
                c = cols_of(var)
                order = np.lexsort((c["codes"], keys))
                skeys = keys[order]
                scodes = c["codes"][order]
                # adjacent-unique over sorted (group, code) pairs — the
                # frontier_dedup kernel with an empty visited set; codes are
                # shifted by one so NULL (-1) stays in the kernel's
                # non-negative pair domain
                t0 = time.perf_counter()
                uniq = np.asarray(ops.frontier_dedup(
                    skeys, scodes + np.int32(1), _EMPTY_I32, _EMPTY_I32,
                    backend=self.backend,
                ), dtype=bool)
                self._dd_ms += time.perf_counter() - t0
                self._dd_calls += 1
                keep = uniq & (scodes >= 0)  # first occurrence AND bound
                svals = c["vals"][order]
                d = (skeys, scodes, svals, keep)
                dsort_cache[var] = d
            return d

        def job(var: Optional[int], stat: str) -> np.ndarray:
            key = (-1 if var is None else var, stat)
            out = job_cache.get(key)
            if out is not None:
                return out
            if stat == "cnt":
                out = self._reduce(keys, None, "count", seg)
            elif stat in ("bnd", "nn", "sum", "min", "max"):
                c = cols_of(var)
                if stat == "bnd":
                    out = self._reduce(
                        keys, (c["codes"] >= 0).astype(np.float64), "sum", seg)
                elif stat == "nn":
                    out = self._reduce(keys, c["valid"].astype(np.float64), "sum", seg)
                elif stat == "sum":
                    out = self._reduce(
                        keys, np.where(c["valid"], c["vals"], 0.0), "sum", seg)
                elif stat == "min":
                    out = self._reduce(
                        keys, np.where(c["valid"], c["vals"], np.inf), "min", seg)
                else:
                    out = self._reduce(
                        keys, np.where(c["valid"], c["vals"], -np.inf), "max", seg)
            else:  # distinct stats run over the (group, code)-sorted batch
                skeys, _, svals, keep = dsort_of(var)
                if stat == "dbnd":
                    out = self._reduce(skeys, keep.astype(np.float64), "sum", seg)
                elif stat == "dnn":
                    dv = keep & ~np.isnan(svals)
                    out = self._reduce(skeys, dv.astype(np.float64), "sum", seg)
                else:  # dsum
                    dv = keep & ~np.isnan(svals)
                    out = self._reduce(skeys, np.where(dv, svals, 0.0), "sum", seg)
            assert len(out) == n_runs
            job_cache[key] = out
            return out

        stats = [
            {stat: job(a.var, stat) for stat in need}
            for a, need in zip(self.aggs, self._needs)
        ]
        dinfo = {
            ai: (dsort_of(self.aggs[ai].var)[1], dsort_of(self.aggs[ai].var)[3])
            for ai in self._dset_aggs
        }
        return stats, dinfo

    def _consume_weighted(self, keys: np.ndarray, cb: ColumnBatch) -> None:
        """Each run's exact int64 Σ of row weights (its length where no row
        carries one). A run that continues the previous batch's last group
        adds into it: the input is sorted by the key."""
        run_keys, starts, lengths = vecops.run_boundaries(keys)
        w = row_weights(cb.var_ids, cb.columns[:, : cb.n_rows])
        sums = lengths.astype(np.int64) if w is None else np.add.reduceat(w, starts)
        if self._out_keys and self._out_keys[-1][-1] == run_keys[0]:
            self._out_w[-1][-1] += sums[0]
            run_keys, sums = run_keys[1:], sums[1:]
        if len(run_keys):
            self._out_keys.append(run_keys.copy())
            self._out_w.append(sums)

    def _consume_batch(self, keys: np.ndarray, cb: ColumnBatch) -> None:
        run_keys, starts, lengths = vecops.run_boundaries(keys)
        n_runs = len(run_keys)
        if n_runs == 0:
            return
        self._runs += n_runs
        # one boundary derivation per batch, shared by every reduction
        seg_ids = (
            np.repeat(np.arange(n_runs), lengths)
            if any(a.var is not None for a in self.aggs)
            else None
        )
        stats, dinfo = self._batch_stats(
            keys, cb, n_runs, seg=(run_keys, lengths, seg_ids)
        )
        i0 = 0
        if self._carry.key is not None:
            if int(run_keys[0]) == self._carry.key:
                # first run continues the open group: fold its partials in
                self._merge_run(stats, dinfo, 0, starts, lengths)
                i0 = 1
                if n_runs > 1:
                    self._close_carry()
            else:
                self._close_carry()
        last = n_runs - 1
        if last > i0:
            # every interior run is provably complete: finalize vectorized
            sl = slice(i0, last)
            self._out_keys.append(run_keys[sl].copy())
            for ai, a in enumerate(self.aggs):
                part = {k: v[sl] for k, v in stats[ai].items()}
                self._out_vals[ai].append(self._final(a, part))
        if last >= i0:
            # the last run may span the batch boundary: it becomes the carry
            self._carry = self._open_carry(int(run_keys[last]))
            self._merge_run(stats, dinfo, last, starts, lengths)

    def _open_carry(self, key: int) -> _Carry:
        return _Carry(
            key=key,
            stats=[
                {s: _SCALAR_INIT[s] for s in need if s not in _DISTINCT_STATS}
                for need in self._needs
            ],
            dcodes={},
        )

    def _merge_run(self, stats, dinfo, r: int, starts, lengths) -> None:
        c = self._carry
        for ai in range(len(self.aggs)):
            st = c.stats[ai]
            for k, arr in stats[ai].items():
                if k in _DISTINCT_STATS:
                    continue  # folded through the code set below
                if k == "min":
                    st["min"] = min(st["min"], float(arr[r]))
                elif k == "max":
                    st["max"] = max(st["max"], float(arr[r]))
                else:
                    st[k] += float(arr[r])
            if ai in dinfo:
                scodes, keep = dinfo[ai]
                s, e = int(starts[r]), int(starts[r] + lengths[r])
                run_codes = scodes[s:e][keep[s:e]]  # sorted unique by constr.
                c.dcodes.setdefault(ai, []).append(run_codes.copy())

    def _close_carry(self) -> None:
        c = self._carry
        if c.key is None:
            return
        self._out_keys.append(np.asarray([c.key], dtype=np.int32))
        for ai, a in enumerate(self.aggs):
            st = dict(c.stats[ai])
            if ai in self._dset_aggs:
                chunks = c.dcodes.get(ai)
                codes = (
                    np.unique(np.concatenate(chunks)) if chunks else _EMPTY_I32
                )
                if not len(codes):
                    st.update(dbnd=0.0, dnn=0.0, dsum=0.0)
                else:
                    vals = self.dictionary.numeric_of(codes)
                    ok = ~np.isnan(vals)
                    st.update(
                        dbnd=float(len(codes)),
                        dnn=float(ok.sum()),
                        dsum=float(vals[ok].sum()) if ok.any() else 0.0,
                    )
            part = {k: np.asarray([v], dtype=np.float64) for k, v in st.items()}
            self._out_vals[ai].append(self._final(a, part))
        self._carry = _Carry()

    @staticmethod
    def _final(a: AggSpec, st: Dict[str, np.ndarray]) -> np.ndarray:
        """Vectorized finalization: per-run float64 results, NaN marking an
        UNBOUND output (mapped to NULL_ID at encode time, never a NaN term)."""
        if a.var is None:
            return st["cnt"]
        if a.func == "count":
            return st["dbnd"] if a.distinct else st["bnd"]
        if a.func == "sum":
            return st["dsum"] if a.distinct else st["sum"]
        if a.func == "min":
            return np.where(st["nn"] > 0, st["min"], np.nan)
        if a.func == "max":
            return np.where(st["nn"] > 0, st["max"], np.nan)
        if a.func == "avg":
            num = st["dsum"] if a.distinct else st["sum"]
            den = st["dnn"] if a.distinct else st["nn"]
            return np.where(den > 0, num / np.maximum(den, 1.0), np.nan)
        raise ValueError(a.func)

    # -- emission ----------------------------------------------------------------

    def _encode(self, vals: np.ndarray) -> np.ndarray:
        """Bulk result encoding: one dictionary.encode per *distinct* value
        (not per group), mapped back with one vectorized take; NaN rows
        (unbound aggregates) become NULL_ID."""
        codes = np.full(len(vals), NULL_ID, dtype=np.int32)
        ok = ~np.isnan(vals)
        if ok.any():
            uniq, inv = np.unique(vals[ok], return_inverse=True)
            ids = np.asarray(
                [
                    self.dictionary.encode(
                        int(u) if float(u).is_integer() else float(u)
                    )
                    for u in uniq
                ],
                dtype=np.int32,
            )
            codes[ok] = ids[inv]
        return codes

    def _encode_sums(self, sums: np.ndarray) -> List[np.ndarray]:
        """Weighted output columns: each COUNT(*) as its exact encoded
        integer, then the fold's weight pair."""
        cols: List[np.ndarray] = []
        if self.aggs:
            uniq, inv = np.unique(sums, return_inverse=True)
            ids = np.asarray([self.dictionary.encode(int(u)) for u in uniq], dtype=np.int32)
            cols = [ids[inv]] * len(self.aggs)
        return cols + (list(weight_pair(sums)) if self.weight else [])

    def _encoded_keys(self) -> np.ndarray:
        """The output's group keys, the input drained and the output
        encoded on the first call."""
        if not self._drained:
            self._consume_all()
        if self._enc_keys is None:
            self._enc_keys = (
                np.concatenate(self._out_keys) if self._out_keys else _EMPTY_I32
            )
            if self.weighted:
                self._enc_cols = self._encode_sums(
                    np.concatenate(self._out_w) if self._out_w
                    else np.zeros(0, dtype=np.int64)
                )
            else:
                self._enc_cols = [
                    self._encode(
                        np.concatenate(v) if v else np.zeros(0, dtype=np.float64)
                    )
                    for v in self._out_vals
                ]
        return self._enc_keys

    def _skip(self, var: int, target: int) -> None:
        keys = self._encoded_keys()
        self._emitted += int(np.searchsorted(keys[self._emitted:], target))

    def _next(self) -> Optional[ColumnBatch]:
        n = len(self._encoded_keys())
        if self._emitted >= n:
            return None
        hi = min(self._emitted + self.batch_size, n)
        sl = slice(self._emitted, hi)
        cols = [self._enc_keys[sl]] if self.g is not None else []
        cols.extend(c[sl] for c in self._enc_cols)
        self._emitted = hi
        return ColumnBatch.from_columns(self.var_ids(), cols, self.g, pool=self.pool)

    def _reset(self) -> None:
        self.child.reset()
        self._out_keys = []
        self._out_w = []
        self._out_vals = [[] for _ in self.aggs]
        self._carry = _Carry()
        self._enc_keys = None
        self._enc_cols = []
        self._emitted = 0
        self._drained = False
        self._sr_calls = 0
        self._sr_ms = 0.0
        self._dd_calls = 0
        self._dd_ms = 0.0
        self._runs = 0


# synthetic variable id for the packed composite group key (never collides
# with parser-assigned ids, which are non-negative)
_GID = -1


class SortGroupBy(BatchOperator):
    """General GROUP BY (multi-var or unsorted input): drain only the
    needed columns from pooled batches, sort ONCE by a packed int64
    composite key (vecops.pack_group_keys), assign dense group ids, and
    stream the sorted runs through StreamingGroupBy. The output is in
    the group keys' lexicographic order, so sorted by the first."""

    def __init__(
        self,
        child: BatchOperator,
        group_vars: Sequence[int],
        aggs: Sequence[AggSpec],
        dictionary: Dictionary,
        batch_size: int = MAX_BATCH,
        pool: Optional[BatchPool] = None,
        backend: Optional[str] = None,
        weight: Sequence[int] = (),
    ):
        self.child = child
        self.group_vars = tuple(group_vars)
        self.aggs = list(aggs)
        self.dictionary = dictionary
        self.batch_size = batch_size
        self.pool = pool
        self.backend = backend
        self.weight = tuple(weight)
        self._src: Optional[BatchOperator] = None
        self._stream: Optional[StreamingGroupBy] = None
        super().__init__(_group_name(self.weight), f"by={self.group_vars} (sort-based)")

    def var_ids(self) -> Tuple[int, ...]:
        return self.group_vars + tuple(a.out for a in self.aggs) + self.weight

    def sorted_by(self) -> Optional[int]:
        return self.group_vars[0] if self.group_vars else None

    def children(self) -> List[BatchOperator]:
        return [self.child]

    def _drain_needed(self, need: Tuple[int, ...]) -> np.ndarray:
        """Materialize only the grouping + aggregate input columns,
        recycling every consumed batch through the pool."""
        blocks = []
        while True:
            b = self.child.next_batch()
            if b is None:
                break
            cb = b.compact()
            if cb.n_rows:
                idx = [cb.col_index(v) for v in need]
                blocks.append(cb.columns[idx, : cb.n_rows])  # fancy-index copy
            cb.release()
        if blocks:
            return np.concatenate(blocks, axis=1)
        return np.zeros((len(need), 0), dtype=np.int32)

    def _need_vars(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """(grouping + aggregate input columns, the aggregate inputs); the
        input's row weights count as aggregate inputs."""
        avars = tuple(
            dict.fromkeys(a.var for a in self.aggs if a.var is not None)
        ) + tuple(v for v in self.child.var_ids() if is_weight_var(v))
        return tuple(dict.fromkeys(self.group_vars + avars)), avars

    def _aggregate_block(
        self, cols: np.ndarray, need: Tuple[int, ...], avars: Tuple[int, ...]
    ) -> np.ndarray:
        """Sort-based aggregation of one in-memory block: sort ONCE by the
        packed composite key, assign dense gids, stream the runs through
        StreamingGroupBy, and translate gids back to group-key values via
        each group's first sorted row. Returns an (n_out_vars, n_groups)
        block. Shared by the whole-input path below and the per-partition
        path (PartitionedGroupBy): group keys never span partitions, so
        per-partition blocks concatenate into the global result."""
        n = cols.shape[1]
        key_rows = cols[: 0] if not self.group_vars else cols[
            [need.index(v) for v in self.group_vars]
        ]
        if self.group_vars and n:
            packed = vecops.pack_group_keys(key_rows)
            order = np.argsort(packed, kind="stable")
            cols = cols[:, order]
            key_rows = cols[[need.index(v) for v in self.group_vars]]
            _, starts, lengths = vecops.run_boundaries(packed[order])
            gid = np.repeat(
                np.arange(len(starts), dtype=np.int32), lengths
            )
        else:
            gid = np.zeros(n, dtype=np.int32)
            starts = np.zeros(1 if n else 0, dtype=np.int64)

        inner = np.concatenate(
            [gid[None, :], cols[[need.index(v) for v in avars]]], axis=0
        ) if avars else gid[None, :]
        inner_src = MaterializedSource(
            (_GID,) + avars, inner, _GID, self.batch_size,
            name="GroupSortBuffer", pool=self.pool,
        )
        self._stream = StreamingGroupBy(
            inner_src, _GID, self.aggs, self.dictionary, self.batch_size,
            backend=self.backend, weight=self.weight,
        )
        # drain the stream (small: one row per group), then translate the
        # dense gid back to the group-key column values via each group's
        # first sorted row
        svars, scols = materialize(self._stream)
        gids = scols[0]
        first_row = starts[gids] if n else np.zeros(0, dtype=np.int64)
        out_cols = [kr[first_row] for kr in key_rows]
        out_cols.extend(scols[1:])
        for k, v in self._stream.stats.extra.items():
            if k.endswith("_ms") or isinstance(v, (int, float)):
                self.stats.extra[k] = self.stats.extra.get(k, 0) + v
            else:
                self.stats.extra[k] = v
        return (
            np.stack(out_cols, axis=0).astype(np.int32)
            if out_cols
            else np.zeros((0, 0), dtype=np.int32)
        )

    def _ensure(self) -> BatchOperator:
        if self._src is not None:
            return self._src
        need, avars = self._need_vars()
        cols = self._drain_needed(need)
        block = self._aggregate_block(cols, need, avars)
        self._src = MaterializedSource(
            self.var_ids(), block, self.sorted_by(), self.batch_size,
            name="GroupOut", pool=self.pool,
        )
        return self._src

    def _next(self) -> Optional[ColumnBatch]:
        return self._ensure().next_batch()

    def _skip(self, var: int, target: int) -> None:
        self._ensure().skip(var, target)

    def _reset(self) -> None:
        self.child.reset()
        self._src = None
        self._stream = None


class StreamingDistinct(BatchOperator):
    """DISTINCT over input sorted by its (single) visible variable, using
    skip() to scroll past duplicates in storage (paper §3.3)."""

    def __init__(self, child: BatchOperator, var: int, use_skip: bool = True):
        assert child.sorted_by() == var
        self.child = child
        self.var = var
        self.use_skip = use_skip and child.supports_skip()
        self._last: Optional[int] = None
        super().__init__("Distinct", f"(?v{var}) streaming")

    def var_ids(self) -> Tuple[int, ...]:
        return (self.var,)

    def sorted_by(self) -> Optional[int]:
        return self.var

    def children(self) -> List[BatchOperator]:
        return [self.child]

    def _next(self) -> Optional[ColumnBatch]:
        while True:
            b = self.child.next_batch()
            if b is None:
                return None
            fb = b.compact()
            cb = fb.project((self.var,))
            fb.release()  # project copied the kept column
            if cb.n_rows == 0:
                continue
            keys = cb.column(self.var)
            run_keys, starts, _ = vecops.run_boundaries(keys)
            if self._last is not None:
                keep = run_keys != self._last
                run_keys, starts = run_keys[keep], starts[keep]
            if len(run_keys) == 0:
                continue
            self._last = int(run_keys[-1])
            if self.use_skip:
                # scroll the child past the last seen value
                self.child.skip(self.var, self._last + 1)
            return ColumnBatch.from_columns((self.var,), [run_keys], self.var)

    def _skip(self, var: int, target: int) -> None:
        self.child.skip(var, target)

    def _reset(self) -> None:
        self.child.reset()
        self._last = None


class SortDistinct(BatchOperator):
    """General DISTINCT: materialize + unique rows (sort-based)."""

    def __init__(self, child: BatchOperator, batch_size: int = MAX_BATCH):
        self.child = child
        self.batch_size = batch_size
        self._src: Optional[MaterializedSource] = None
        super().__init__("Distinct", "(sort-based)")

    def var_ids(self) -> Tuple[int, ...]:
        return self.child.var_ids()

    def children(self) -> List[BatchOperator]:
        return [self.child]

    def _ensure(self) -> MaterializedSource:
        if self._src is None:
            vars_, cols = materialize(self.child)
            uniq = np.unique(cols.T, axis=0).T if cols.shape[1] else cols
            sb = vars_[0] if len(vars_) == 1 and uniq.shape[1] else None
            self._src = MaterializedSource(
                vars_, uniq.astype(np.int32), sb, self.batch_size, name="DistinctBuffer"
            )
        return self._src

    def _next(self) -> Optional[ColumnBatch]:
        return self._ensure().next_batch()

    def _reset(self) -> None:
        self.child.reset()
        self._src = None


class PartitionedGroupBy(SortGroupBy):
    """GROUP BY over the partitioned substrate (DESIGN.md §15): fan the
    input out by group key into a budget/spill-aware PartitionedRelation,
    then run the sort-based block aggregation one partition at a time.
    Each group's rows land in exactly one partition (same key tuple ->
    same partition id), so per-partition outputs concatenate into the
    global result — the whole input is never sorted or resident at once,
    unlike the parent's single-argsort path. A group with no aggregate
    (a fold) sorts its output by its keys at the end, as the planner
    claims it is."""

    def __init__(
        self,
        child: BatchOperator,
        group_vars: Sequence[int],
        aggs: Sequence[AggSpec],
        dictionary: Dictionary,
        batch_size: int = MAX_BATCH,
        pool: Optional[BatchPool] = None,
        backend: Optional[str] = None,
        memory_budget: Optional[int] = None,
        spill_dir: Optional[str] = None,
        n_parts: int = 16,
        weight: Sequence[int] = (),
    ):
        assert group_vars, "partitioned grouping needs group keys"
        super().__init__(
            child, group_vars, aggs, dictionary, batch_size, pool, backend,
            weight,
        )
        self.memory_budget = memory_budget
        self.spill_dir = spill_dir
        self.n_parts = max(2, n_parts)
        self._rel: Optional[PartitionedRelation] = None
        self.stats.detail = f"by={self.group_vars} (partitioned)"

    def sorted_by(self) -> Optional[int]:
        return None if self.aggs else self.group_vars[0]

    def _partition_input(self, need: Tuple[int, ...]) -> PartitionedRelation:
        rel = PartitionedRelation(
            len(need), self.n_parts, self.spill_dir, self.memory_budget,
            self.pool,
        )
        gidx = [need.index(v) for v in self.group_vars]
        while True:
            b = self.child.next_batch()
            if b is None:
                break
            cb = b.compact()
            if cb.n_rows:
                cols = np.stack([cb.column(v) for v in need])
                rel.append(cols, partition_ids_multi(cols[gidx], self.n_parts))
            cb.release()
        return rel

    def _ensure(self) -> BatchOperator:
        if self._src is not None:
            return self._src
        need, avars = self._need_vars()
        self._rel = self._partition_input(need)
        blocks = []
        for p in range(self.n_parts):
            part = self._rel.take(p)
            if part.shape[1]:
                blocks.append(self._aggregate_block(part, need, avars))
        block = (
            np.concatenate(blocks, axis=1)
            if blocks
            else np.zeros((len(self.var_ids()), 0), dtype=np.int32)
        )
        if not self.aggs and block.shape[1]:
            keys = block[: len(self.group_vars)]
            block = block[:, np.argsort(vecops.pack_group_keys(keys), kind="stable")]
        self.stats.extra["grace_partitions"] = self.n_parts
        self.stats.extra["spill_bytes"] = self._rel.spill_bytes
        self.stats.extra["spill_files"] = self._rel.spill_files
        self._src = MaterializedSource(
            self.var_ids(), block, self.sorted_by(), self.batch_size,
            name="GroupOut", pool=self.pool,
        )
        return self._src

    def _close(self) -> None:
        if self._rel is not None:
            self._rel.close()

    def _reset(self) -> None:
        self._close()
        self._rel = None
        super()._reset()


class PartitionedDistinct(BatchOperator):
    """General DISTINCT over the partitioned substrate: fan rows out by
    ALL visible columns, dedup each partition independently (identical
    rows share a partition id by construction), and concatenate. Output
    order is partition-major — never claimed sorted, unlike SortDistinct
    whose np.unique output is globally ordered."""

    def __init__(
        self,
        child: BatchOperator,
        batch_size: int = MAX_BATCH,
        pool: Optional[BatchPool] = None,
        memory_budget: Optional[int] = None,
        spill_dir: Optional[str] = None,
        n_parts: int = 16,
    ):
        self.child = child
        self.batch_size = batch_size
        self.pool = pool
        self.memory_budget = memory_budget
        self.spill_dir = spill_dir
        self.n_parts = max(2, n_parts)
        self._rel: Optional[PartitionedRelation] = None
        self._src: Optional[MaterializedSource] = None
        super().__init__("Distinct", "(partitioned)")

    def var_ids(self) -> Tuple[int, ...]:
        return self.child.var_ids()

    def children(self) -> List[BatchOperator]:
        return [self.child]

    def _ensure(self) -> MaterializedSource:
        if self._src is not None:
            return self._src
        nv = len(self.var_ids())
        self._rel = PartitionedRelation(
            nv, self.n_parts, self.spill_dir, self.memory_budget, self.pool
        )
        vs = self.var_ids()
        while True:
            b = self.child.next_batch()
            if b is None:
                break
            cb = b.compact()
            if cb.n_rows:
                cols = np.stack([cb.column(v) for v in vs])
                self._rel.append(
                    cols, partition_ids_multi(cols, self.n_parts)
                )
            cb.release()
        blocks = []
        for p in range(self.n_parts):
            part = self._rel.take(p)
            if part.shape[1]:
                blocks.append(np.unique(part.T, axis=0).T)
        uniq = (
            np.concatenate(blocks, axis=1).astype(np.int32)
            if blocks
            else np.zeros((nv, 0), dtype=np.int32)
        )
        self.stats.extra["grace_partitions"] = self.n_parts
        self.stats.extra["spill_bytes"] = self._rel.spill_bytes
        self.stats.extra["spill_files"] = self._rel.spill_files
        self._src = MaterializedSource(
            vs, uniq, None, self.batch_size, name="DistinctBuffer",
            pool=self.pool,
        )
        return self._src

    def _next(self) -> Optional[ColumnBatch]:
        return self._ensure().next_batch()

    def _close(self) -> None:
        if self._rel is not None:
            self._rel.close()

    def _reset(self) -> None:
        self._close()
        self._rel = None
        self.child.reset()
        self._src = None
