"""Vectorized data-plane primitives — numpy reference backend.

Every per-batch computation in the BARQ operators funnels through these
functions. They have three interchangeable implementations:

  * this module — numpy, the data plane on a host without a TPU, and the
    oracle;
  * ``repro.kernels.ref`` — pure-jnp mirrors (jit-compiled);
  * ``repro.kernels.*`` — Pallas TPU kernels, the data plane on a TPU
    (validated against this module in interpret mode).

``repro.kernels.ops`` dispatches between them. Operators never hand-roll
per-row loops — that is the point of the paper.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# run / group detection (merge-join Probe phase, paper §3.2)
# ---------------------------------------------------------------------------


def run_boundaries(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Runs of equal values in a sorted key column.

    Returns (values, starts, lengths): values[i] is the key of run i which
    occupies keys[starts[i] : starts[i] + lengths[i]].
    """
    n = len(keys)
    if n == 0:
        e = np.zeros(0, dtype=np.int32)
        return e, e, e
    is_start = np.empty(n, dtype=bool)
    is_start[0] = True
    np.not_equal(keys[1:], keys[:-1], out=is_start[1:])
    starts = np.nonzero(is_start)[0].astype(np.int32)
    lengths = np.diff(np.append(starts, n)).astype(np.int32)
    return keys[starts].astype(np.int32), starts, lengths


def probe_groups(
    lvals: np.ndarray,
    rvals: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """Match left runs against right runs by key (both sorted ascending,
    values unique within each side). Returns (left_run_idx, right_run_idx)
    for every matching pair — the paper's 'input groups'."""
    pos = np.searchsorted(rvals, lvals, side="left")
    pos_c = np.minimum(pos, max(len(rvals) - 1, 0))
    hit = (len(rvals) > 0) & (rvals[pos_c] == lvals) if len(rvals) else np.zeros(
        len(lvals), dtype=bool
    )
    li = np.nonzero(hit)[0].astype(np.int32)
    return li, pos[li].astype(np.int32)


# ---------------------------------------------------------------------------
# cross-product materialization (merge-join Build phase, paper §3.2)
# ---------------------------------------------------------------------------


def group_output_offsets(
    llens: np.ndarray, rlens: np.ndarray
) -> np.ndarray:
    """cum[i] = total output rows of groups < i; cum[-1] = grand total.
    Output rows of group g = left_len[g] * right_len[g] (cross product)."""
    counts = llens.astype(np.int64) * rlens.astype(np.int64)
    return np.concatenate([[0], np.cumsum(counts)])


def expand_cross(
    lstarts: np.ndarray,
    llens: np.ndarray,
    rstarts: np.ndarray,
    rlens: np.ndarray,
    cum: np.ndarray,
    base: int,
    count: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Materialize output slots [base, base+count) of the grouped cross
    product as (left_row_idx, right_row_idx) gather indices.

    For global output slot t: find its group g (binary search over cum),
    within-group offset w = t - cum[g]; then
        left_row  = lstarts[g] + w // rlens[g]     (left expanded)
        right_row = rstarts[g] + w %  rlens[g]     (right repeated)
    — exactly the paper's 'expand left by right range length, repeat right
    by left range length', computed slot-parallel so the TPU kernel is a
    pure map over the output block.
    """
    # the slots [base, base+count) are contiguous, so instead of a per-slot
    # binary search the group ids are a run-length expansion of the (few)
    # groups the window spans: O(count + groups) instead of O(count log G)
    hi = base + count
    g0 = int(np.searchsorted(cum, base, side="right")) - 1
    g1 = int(np.searchsorted(cum, hi, side="left"))
    seg = np.minimum(cum[g0 + 1 : g1 + 1], hi) - np.maximum(cum[g0:g1], base)
    g = np.repeat(np.arange(g0, g1, dtype=np.intp), seg)
    # stay in int32 while the offsets fit — int64 div/mod is ~2x slower and
    # dominates the Build phase otherwise
    dt = np.int32 if int(cum[-1]) < np.iinfo(np.int32).max else np.int64
    t = np.arange(base, hi, dtype=dt)
    w = t - cum[g].astype(dt)
    # unit-length runs need no div/mod: the within-group offset walks the
    # other side directly. Lookup joins always hit the llens==1 case (every
    # probe row is a length-1 left range).
    if llens[g0:g1].max(initial=1) == 1:
        li = lstarts[g]
        ri = rstarts[g] + w.astype(np.int32)
    elif rlens[g0:g1].max(initial=1) == 1:
        li = lstarts[g] + w.astype(np.int32)
        ri = rstarts[g]
    else:
        rl = rlens[g].astype(dt)
        li = lstarts[g] + (w // rl).astype(np.int32)
        ri = rstarts[g] + (w % rl).astype(np.int32)
    return np.asarray(li, dtype=np.int32), np.asarray(ri, dtype=np.int32)


# ---------------------------------------------------------------------------
# fused gather-emit (merge/lookup join Build emission, DESIGN.md §2.3)
# ---------------------------------------------------------------------------

_NULL = np.int32(-1)  # == batch.NULL_ID (kept local to avoid an import cycle)


def _take(src: np.ndarray, idx: np.ndarray, out: np.ndarray) -> None:
    """Gather src[idx] straight into ``out``, skipping the temporary that
    fancy indexing would allocate. Falls back when the destination isn't
    contiguous (np.take requires it)."""
    if out.flags.c_contiguous and src.flags.c_contiguous:
        np.take(src, idx, out=out, mode="clip")
    else:
        out[...] = src[idx]


def gather_emit(
    lcols: np.ndarray,
    rcols: Optional[np.ndarray],
    li: np.ndarray,
    ri: Optional[np.ndarray],
    lsel: Tuple[int, ...],
    rsel: Tuple[int, ...],
    pairs: Tuple[Tuple[int, int], ...],
    out: Optional[np.ndarray] = None,
    out_offset: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Fused join emission: gather + NULL-extend + secondary-key equality.

    One primitive replaces the per-column Python loops and the intermediate
    whole-window materializations of the join emit paths:

      lcols: (KL, NL) int32 source columns (left / probe side);
      rcols: (KR, NR) int32 source columns (right / build side), or None;
      li:    (C,) int32 row gather indices into lcols;
      ri:    (C,) int32 row gather indices into rcols, or None. ri == -1
             marks a *virtual NULL row* (left_outer padding): right outputs
             become NULL_ID and pair comparisons auto-pass for that slot.
      lsel:  source-row ids of lcols to emit, in output order. A -1 entry
             emits a NULL_ID column (schema alignment in concat_batches).
      rsel:  source-row ids of rcols to emit after the left block.
      pairs: (l_row, r_row) secondary join-key comparisons (paper §3.2
             Multiple Join Keys) folded into the returned validity mask.
      out:   optional (>=len(lsel)+len(rsel), >=out_offset+C) destination;
             rows [0, K) of out[:, out_offset:out_offset+C] are written in
             place (the pooled-buffer zero-copy path). A fresh array is
             allocated when omitted.

    Returns (out_block, mask): the (K, C) emitted block and the (C,) bool
    combined validity mask.
    """
    C = int(len(li))
    K = len(lsel) + len(rsel)
    if out is None:
        out = np.empty((K, C), dtype=np.int32)
        view = out
    else:
        view = out[:K, out_offset : out_offset + C]

    if ri is None:
        rvalid = None
        ric = None
    else:
        rvalid = ri >= 0
        if rvalid.all():
            rvalid = None  # fast path: no virtual rows
            ric = ri
        else:
            ric = np.where(rvalid, ri, 0)

    for j, row in enumerate(lsel):
        if row < 0:
            view[j] = _NULL
        else:
            _take(lcols[row], li, view[j])
    r_empty = rcols is None or rcols.shape[1] == 0
    for j, row in enumerate(rsel):
        dst = view[len(lsel) + j]
        if row < 0 or r_empty:
            dst[:] = _NULL
        elif rvalid is None:
            _take(rcols[row], ric, dst)
        else:
            np.copyto(dst, np.where(rvalid, rcols[row, ric], _NULL))

    mask = np.ones(C, dtype=bool)
    for lrow, rrow in pairs:
        lv = lcols[lrow, li]
        rv = np.zeros(C, dtype=np.int32) if r_empty else rcols[rrow, ric]
        eq = lv == rv
        mask &= eq if rvalid is None else (~rvalid | eq)
    return view, mask


# ---------------------------------------------------------------------------
# frontier dedup (property-path BFS rounds, DESIGN.md §8)
# ---------------------------------------------------------------------------


def _pair_key(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """Composite int64 sort key for non-negative int32 (hi, lo) pairs."""
    return (hi.astype(np.int64) << 32) | lo.astype(np.int64)


def frontier_dedup(
    cand_hi: np.ndarray,
    cand_lo: np.ndarray,
    vis_hi: np.ndarray,
    vis_lo: np.ndarray,
) -> np.ndarray:
    """Validity mask over a lexicographically sorted candidate frontier.

    Inputs are (source, node) pairs as two int32 columns, both the
    candidate batch and the visited set sorted lexicographically by
    (hi, lo). mask[j] is True iff candidate j is the first occurrence of
    its pair within the batch (adjacent-unique) AND the pair is absent
    from the visited set — the semi-naive delta of a BFS round. With an
    empty visited set this is plain sort-unique (relation dedup).
    """
    c = int(len(cand_hi))
    mask = np.ones(c, dtype=bool)
    if c == 0:
        return mask
    np.logical_or(
        cand_hi[1:] != cand_hi[:-1], cand_lo[1:] != cand_lo[:-1], out=mask[1:]
    )
    if len(vis_hi):
        key_c = _pair_key(cand_hi, cand_lo)
        key_v = _pair_key(vis_hi, vis_lo)
        pos = np.searchsorted(key_v, key_c, side="left")
        inb = pos < len(key_v)
        member = np.zeros(c, dtype=bool)
        member[inb] = key_v[np.minimum(pos[inb], len(key_v) - 1)] == key_c[inb]
        mask &= ~member
    return mask


def merge_sorted_pairs(
    a_hi: np.ndarray, a_lo: np.ndarray, b_hi: np.ndarray, b_lo: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Merge two lexicographically sorted, mutually disjoint pair sets into
    one sorted pair set (the visited-set growth step; O(|a| + |b|)). The
    result never aliases ``b`` — callers pass views into recycled buffers."""
    if not len(b_hi):
        return a_hi, a_lo
    if not len(a_hi):
        return b_hi.copy(), b_lo.copy()
    pos = np.searchsorted(_pair_key(a_hi, a_lo), _pair_key(b_hi, b_lo))
    return (
        np.insert(a_hi, pos, b_hi),
        np.insert(a_lo, pos, b_lo),
    )


# ---------------------------------------------------------------------------
# sorted search (vectorized skip()/seek, paper §3.2 Skip phase)
# ---------------------------------------------------------------------------


def sorted_search(keys: np.ndarray, queries: np.ndarray, side: str = "left") -> np.ndarray:
    """Positions of ``queries`` in sorted ``keys`` (galloping seek)."""
    return np.searchsorted(keys, queries, side=side).astype(np.int32)


# ---------------------------------------------------------------------------
# selection-vector ops (paper §3.1)
# ---------------------------------------------------------------------------


def compact_indices(mask: np.ndarray) -> np.ndarray:
    """Selection vector from validity mask (prefix-sum compaction)."""
    return np.nonzero(mask)[0].astype(np.int32)


def multiway_equal_mask(cols_l: np.ndarray, cols_r: np.ndarray) -> np.ndarray:
    """Vectorized secondary-join-key equality (paper §3.2 Multiple Join
    Keys): rows where every secondary key pair matches."""
    return np.all(cols_l == cols_r, axis=0)


# ---------------------------------------------------------------------------
# composite group keys (multi-key GROUP BY, DESIGN.md §10)
# ---------------------------------------------------------------------------


def pack_group_keys(
    key_cols: np.ndarray,
    spans: Optional[Sequence[int]] = None,
) -> Optional[np.ndarray]:
    """Pack a (k, n) block of int32 group-key columns (NULL_ID == -1
    allowed) into ONE int64 composite key whose ordering and equality match
    the lexicographic order of the columns — so multi-key grouping needs a
    single-key argsort instead of a k-column lexsort.

    With ``spans=None`` (grouping), columns pack most-significant-first
    with per-column ranges max+2 (codes shift by one so NULL packs as 0).
    When the range product would overflow 63 bits, falls back to a
    lexsort-based dense rank, which preserves both ordering and group
    boundaries.

    With explicit ``spans`` (multi-variable hash-join keys: the packing
    must be identical across probe batches, so the ranges are fixed up
    front from the build side), values at or above their span clamp to the
    span's last slot. Callers must size each span with one spare sentinel
    slot above the build side's maximum shifted value (span >= max+3 for
    codes up to max), so clamped out-of-range probe values land on a slot
    no build key occupies — they can then never falsely match, and
    probe-probe collisions are harmless because probe keys are only ever
    compared against build keys. Returns None when the span product
    overflows 62 bits (the caller falls back to primary-key hashing +
    pairwise verification); the rank fallback is not available because
    ranks are not stable across batches."""
    key_cols = np.asarray(key_cols)
    k, n = key_cols.shape
    assert k >= 1
    if spans is not None:
        assert len(spans) == k
        if math.prod(int(s) for s in spans) >= 1 << 62:
            return None
        packed = np.minimum(key_cols[0].astype(np.int64) + 1, spans[0] - 1)
        for c, s in zip(key_cols[1:], spans[1:]):
            packed = packed * int(s) + np.minimum(
                c.astype(np.int64) + 1, int(s) - 1
            )
        return packed
    packed = key_cols[0].astype(np.int64) + 1
    span = int(key_cols[0].max(initial=-1)) + 2
    for c in key_cols[1:]:
        r = int(c.max(initial=-1)) + 2
        if span * r >= 1 << 62:
            order = np.lexsort(tuple(key_cols[::-1]))
            srt = key_cols[:, order]
            change = np.zeros(n, dtype=bool)
            if n:
                change[0] = True
                for row in srt:
                    change[1:] |= row[1:] != row[:-1]
            out = np.empty(n, dtype=np.int64)
            out[order] = np.cumsum(change) - 1
            return out
        packed = packed * r + (c.astype(np.int64) + 1)
        span *= r
    return packed


# ---------------------------------------------------------------------------
# sorted segment aggregation (paper §3.3)
# ---------------------------------------------------------------------------

AGG_INIT = {
    "count": 0.0,
    "sum": 0.0,
    "min": np.inf,
    "max": -np.inf,
}


def segment_reduce(
    keys: np.ndarray,
    values: Optional[np.ndarray],
    func: str,
    seg: Optional[Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Per-run aggregate over a batch sorted by ``keys``.

    Returns (run_keys, partials). ``values`` is float64 (already decoded via
    the numeric side-array) or None for COUNT(*). Associative partials merge
    across batches in the streaming operator (paper: count/min/max/avg are
    associative and merge across batches).

    ``seg`` optionally carries precomputed (run_keys, lengths, seg_ids) for
    ``keys`` so a caller issuing one reduction per statistic over the same
    key column (the streaming GROUP BY) skips the per-call boundary
    re-derivation; seg_ids may be None and is derived on demand.
    """
    if seg is None:
        run_keys, _, lengths = run_boundaries(keys)
        seg_ids = None
    else:
        run_keys, lengths, seg_ids = seg
    n_runs = len(run_keys)
    if n_runs == 0:
        return run_keys, np.zeros(0, dtype=np.float64)
    if func == "count":
        return run_keys, lengths.astype(np.float64)
    if seg_ids is None:
        seg_ids = np.repeat(np.arange(n_runs), lengths)
    assert values is not None
    if func == "sum":
        out = np.zeros(n_runs, dtype=np.float64)
        np.add.at(out, seg_ids, values)
    elif func == "min":
        out = np.full(n_runs, np.inf, dtype=np.float64)
        np.minimum.at(out, seg_ids, values)
    elif func == "max":
        out = np.full(n_runs, -np.inf, dtype=np.float64)
        np.maximum.at(out, seg_ids, values)
    else:
        raise ValueError(func)
    return run_keys, out


# ---------------------------------------------------------------------------
# hash partitioning (distributed exchange; DESIGN.md §2.1)
# ---------------------------------------------------------------------------

_HASH_MULT = np.uint32(0x9E3779B1)  # Fibonacci hashing


def hash_partition(keys: np.ndarray, n_parts: int) -> np.ndarray:
    """Multiplicative-hash partition id per key (n_parts power of two)."""
    h = (keys.astype(np.uint32) * _HASH_MULT) >> np.uint32(16)
    return (h & np.uint32(n_parts - 1)).astype(np.int32)


def partition_histogram(part_ids: np.ndarray, n_parts: int) -> np.ndarray:
    return np.bincount(part_ids, minlength=n_parts).astype(np.int32)


# ---------------------------------------------------------------------------
# radix-partitioned hash join primitives (DESIGN.md §11)
# ---------------------------------------------------------------------------
#
# The logical join key is an int32 (hi, lo) pair compared lexicographically:
# single-variable keys pass hi=None (all-zero) and lo=the code column
# (NULL_ID == -1 is an ordinary value that equals itself, matching the
# merge-join and row-engine semantics); multi-variable keys pack through
# pack_group_keys(spans=...) into a non-negative int64 split as
# hi = packed >> 31, lo = packed & 0x7FFFFFFF. hi is always >= 0.

_MIX_MULT = np.uint32(0x85EBCA6B)  # murmur3 fmix constant


def mix_pair(key_hi: Optional[np.ndarray], key_lo: np.ndarray) -> np.ndarray:
    """Fold an (hi, lo) key pair into one int32 hash input; identity for
    single-column keys so their partition ids match radix_partition on the
    raw codes. INT32_MIN is remapped (it is the Pallas radix_partition
    kernel's padding sentinel; single-column inputs are dictionary codes
    >= -1 and can never hit it, but a xor-mix can)."""
    lo = np.asarray(key_lo, dtype=np.int32)
    if key_hi is None:
        return lo
    mixed = (
        lo.view(np.uint32)
        ^ (np.asarray(key_hi, dtype=np.int32).view(np.uint32) * _MIX_MULT)
    ).view(np.int32)
    sentinel = np.iinfo(np.int32).min
    if (mixed == sentinel).any():
        mixed = np.where(mixed == sentinel, np.int32(0), mixed)
    return mixed


def _pair_comp(key_hi: Optional[np.ndarray], key_lo: np.ndarray) -> np.ndarray:
    """int64 composite preserving (hi, lo) lexicographic order (hi >= 0).
    Values are non-negative and < 2^63 (single-column keys < 2^32)."""
    lo64 = np.asarray(key_lo, np.int32).astype(np.int64) + (1 << 31)
    if key_hi is None:
        return lo64
    return (np.asarray(key_hi, np.int32).astype(np.int64) << 32) | lo64


def _pid_shift(n_parts: int) -> int:
    """Bits available for the key below the partition id in a global
    (pid, key) int64 composite."""
    return 63 - max(int(n_parts - 1).bit_length(), 1)


def hash_build_order(
    pid: np.ndarray,
    key_hi: Optional[np.ndarray],
    key_lo: np.ndarray,
    n_parts: int,
) -> np.ndarray:
    """Build-side reorder permutation: rows grouped by partition id, key-
    sorted within each partition — the two-level layout hash_probe
    searches. When the (pid, key) pair fits one int64 word (always for
    single-column keys; pair keys whenever the pack spans leave room for
    the partition bits) this is ONE stable argsort — numpy's stable sort
    on integer dtypes is a radix sort, so the build is O(n), not a
    comparison sort. The rare oversized pair keys fall back to lexsort."""
    lo = np.asarray(key_lo, dtype=np.int32)
    packed = _pair_comp(key_hi, lo)
    shift = _pid_shift(n_parts)
    if key_hi is None or int(packed.max(initial=0)) < (1 << shift):
        comp = (pid.astype(np.int64) << shift) | packed
        return np.argsort(comp, kind="stable").astype(np.int32)
    return np.lexsort((lo, np.asarray(key_hi, np.int32), pid)).astype(np.int32)


def hash_probe_positions(
    spid: np.ndarray,
    skey_hi: Optional[np.ndarray],
    skey_lo: np.ndarray,
    qpid: np.ndarray,
    qkey_hi: Optional[np.ndarray],
    qkey_lo: np.ndarray,
    part_starts: np.ndarray,
    cache: Optional[dict] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """(lo, hi) match-run positions of each probe key in the partitioned
    build layout: build rows [lo[i], hi[i]) carry probe i's exact key.

    The steady-state path folds (pid, key) into one global int64 composite
    and answers both run boundaries with two searchsorted passes; ``cache``
    (one dict per build, threaded through kernels.ops by the operator)
    keeps the build-side composite across probe batches so the per-batch
    cost is the searches alone. Pair keys too wide to share a word with
    the partition bits take a vectorized segmented binary search inside
    each probe's partition slice instead (every iteration advances all
    probes one halving step — O(probes · log max_partition))."""
    n_parts = len(part_starts) - 1
    shift = _pid_shift(n_parts)
    if (
        cache is not None
        and skey_hi is None
        and "tables" not in cache
        and len(skey_lo)
    ):
        # single-column keys are dictionary codes — a dense, bounded
        # domain. When it is small enough, upgrade the partition directory
        # to a direct-addressed run table (the limiting case of radix
        # partitioning: every key its own bucket): probe cost drops from a
        # binary search to two gathers per key. Runs stay contiguous in
        # the (pid, key) layout, so the table just records them.
        max_b = int(skey_lo.max())
        domain = max_b + 2  # +1 shift so NULL_ID (-1) owns slot 0
        if domain <= max(4 * len(skey_lo), 1 << 16):
            is_start = np.empty(len(skey_lo), dtype=bool)
            is_start[0] = True
            np.not_equal(skey_lo[1:], skey_lo[:-1], out=is_start[1:])
            if n_parts > 1:  # equal keys never span partitions; pid breaks runs too
                np.logical_or(
                    is_start[1:], spid[1:] != spid[:-1], out=is_start[1:]
                )
            starts = np.nonzero(is_start)[0].astype(np.int32)
            lengths = np.diff(np.append(starts, len(skey_lo))).astype(np.int32)
            lo_t = np.zeros(domain + 1, np.int32)  # last slot = sentinel
            len_t = np.zeros(domain + 1, np.int32)
            slot = skey_lo[starts].astype(np.int64) + 1
            lo_t[slot] = starts
            len_t[slot] = lengths
            cache["tables"] = (lo_t, len_t, domain)
        else:
            cache["tables"] = None
    if (
        cache is not None
        and skey_hi is None
        and cache.get("tables") is not None
    ):
        lo_t, len_t, domain = cache["tables"]
        idx = qkey_lo.astype(np.int64) + 1
        idx = np.where(idx < domain, idx, domain)  # out-of-domain -> sentinel
        lo = lo_t[idx]
        return lo, lo + len_t[idx]
    if cache is not None and "comp_b" in cache:
        comp_b = cache["comp_b"]
    else:
        packed_b = _pair_comp(skey_hi, skey_lo)
        if skey_hi is None or int(packed_b.max(initial=0)) < (1 << shift):
            comp_b = (spid.astype(np.int64) << shift) | packed_b
        else:
            comp_b = None  # oversized pair keys: segmented search
        if cache is not None:
            cache["comp_b"] = comp_b
    packed_q = _pair_comp(qkey_hi, qkey_lo)
    if comp_b is not None and (
        qkey_hi is None or int(packed_q.max(initial=0)) < (1 << shift)
    ):
        comp_q = (qpid.astype(np.int64) << shift) | packed_q
        lo = np.searchsorted(comp_b, comp_q, side="left")
        hi = np.searchsorted(comp_b, comp_q, side="right")
        return lo.astype(np.int32), hi.astype(np.int32)
    # fallback: per-partition binary search on the (hi, lo) composite,
    # both boundaries advanced in one halving loop
    comp_seg = _pair_comp(skey_hi, skey_lo)
    n_b = max(len(comp_seg), 1)
    seg_lo = part_starts[qpid].astype(np.int64)
    seg_hi = part_starts[qpid + 1].astype(np.int64)
    llo, lhi = seg_lo.copy(), seg_hi.copy()
    rlo, rhi = seg_lo, seg_hi.copy()
    while True:
        l_act = llo < lhi
        r_act = rlo < rhi
        if not (l_act.any() or r_act.any()):
            break
        lmid = (llo + lhi) >> 1
        rmid = (rlo + rhi) >> 1
        lgo = (comp_seg[np.minimum(lmid, n_b - 1)] < packed_q) & l_act
        rgo = (comp_seg[np.minimum(rmid, n_b - 1)] <= packed_q) & r_act
        llo = np.where(lgo, lmid + 1, llo)
        lhi = np.where(l_act & ~lgo, lmid, lhi)
        rlo = np.where(rgo, rmid + 1, rlo)
        rhi = np.where(r_act & ~rgo, rmid, rhi)
    return llo.astype(np.int32), rlo.astype(np.int32)


# ---------------------------------------------------------------------------
# blocked bloom filter (sideways information passing, DESIGN.md §12)
# ---------------------------------------------------------------------------
#
# One uint32 word per block; each key sets two bits of one word, both derived
# from two independent multiplicative hashes of the raw int32 code (NULL_ID
# == -1 hashes like any other value — it equals itself in joins). A probe is
# a member iff both its bits are set in its word: no false negatives, false
# positives bounded by the words-per-key ratio chosen in bloom_n_words.

_BLOOM_MULT2 = np.uint32(0x85EBCA6B)  # murmur3 fmix constant, decorrelates h2


def bloom_n_words(n_keys: int) -> int:
    """Power-of-two word count targeting ~16 bits per key (two probes in a
    32-bit word at half load keeps the false-positive rate around 1-2%)."""
    n = 1
    while n * 2 < max(n_keys, 1) and n < (1 << 20):
        n *= 2
    return n


def bloom_hash(keys: np.ndarray, n_words: int) -> Tuple[np.ndarray, np.ndarray]:
    """(word index, bit pattern) per key — the shared address computation
    every backend must reproduce exactly (parity-swept in test_sip)."""
    u = np.asarray(keys, dtype=np.int32).astype(np.uint32)
    h1 = u * _HASH_MULT
    h2 = u * _BLOOM_MULT2
    word = ((h1 >> np.uint32(18)) & np.uint32(n_words - 1)).astype(np.int32)
    b1 = h1 & np.uint32(31)
    b2 = (h2 >> np.uint32(13)) & np.uint32(31)
    bits = (np.uint32(1) << b1) | (np.uint32(1) << b2)
    return word, bits


def bloom_build(keys: np.ndarray, n_words: int) -> Tuple[np.ndarray, int, int]:
    """(words, lo, hi): the blocked bloom filter plus the min/max code range
    of the build side. An empty build returns the empty range (0, -1)."""
    keys = np.asarray(keys, dtype=np.int32)
    words = np.zeros(n_words, dtype=np.uint32)
    if len(keys) == 0:
        return words, 0, -1
    word, bits = bloom_hash(keys, n_words)
    np.bitwise_or.at(words, word, bits)
    return words, int(keys.min()), int(keys.max())


def bloom_probe(words: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """Membership mask: True where the query's two bits are both set.
    False positives possible, false negatives never."""
    queries = np.asarray(queries, dtype=np.int32)
    word, bits = bloom_hash(queries, len(words))
    return (words[word] & bits) == bits
