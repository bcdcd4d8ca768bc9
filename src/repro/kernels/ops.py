"""Jit'd public wrappers + backend dispatch for the BARQ kernels.

Backends:
  numpy  — repro.core.vecops (the data plane on any host without a TPU,
           and the oracle);
  jax    — repro.kernels.ref jnp mirrors (jit; what XLA-TPU would run
           without custom kernels);
  pallas — the Pallas TPU kernels (the data plane on a TPU), compiled for
           the chip there and run in the TPU interpreter on any other
           platform (validated against both other backends in
           tests/test_kernels.py).

The platform picks the data plane (``default_backend``). A call may name
its backend with backend=...; ``data_plane`` overrides the default for
every dispatch in a block, so one process can run the same queries on
both planes.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import inspect
import time
from typing import Optional, Tuple

import numpy as np

from repro.core import telemetry
from repro.core import vecops
from repro.kernels import tiling

_OVERRIDE: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "kernel_backend", default=None
)


@functools.lru_cache(maxsize=None)
def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def default_backend() -> str:
    """The data plane of a dispatch that names no backend: the innermost
    ``data_plane`` block's, else pallas on a TPU and numpy elsewhere."""
    return _OVERRIDE.get() or ("pallas" if _on_tpu() else "numpy")


@contextlib.contextmanager
def data_plane(plane: str):
    """Run every kernel dispatch that names no backend inside the block on
    ``plane`` (scoped to the current thread or task)."""
    token = _OVERRIDE.set(plane)
    try:
        yield
    finally:
        _OVERRIDE.reset(token)


@functools.lru_cache(maxsize=None)
def _interpret():
    """Pallas kernels compile for the chip on a TPU. Anywhere else they run
    in the TPU interpreter, which keeps the chip's block semantics: it
    refuses, as the chip would miscompute, an output block that the grid
    leaves and comes back to."""
    if _on_tpu():
        return False
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams()

# Process-wide dispatch ledger: every public wrapper below counts one entry
# per call under its kernel name. Observability for tests and benchmarks —
# e.g. a grouped query must show segment_reduce > 0 or the "vectorized
# grouping" claim is hollow (tests/test_aggregate.py pins this).
#
# Since DESIGN.md §13 this Counter is the ``counts`` table of the
# process-global telemetry.KernelLedger. It ALWAYS accumulates; when a
# query-scoped trace is active (telemetry.trace_query), each dispatch is
# additionally attributed — with per-dispatch wall time, by kernel name
# and backend — to that trace's own ledger, so interleaved queries on one
# server never misattribute each other's kernel work.
DISPATCH_COUNTS = telemetry.global_ledger().counts


def dispatch_count(name: Optional[str] = None) -> int:
    """Total kernel dispatches (or for one kernel) since process start /
    last reset — always the process-global view, unaffected by any active
    query-scoped ledger."""
    if name is None:
        return sum(DISPATCH_COUNTS.values())
    return DISPATCH_COUNTS[name]


def reset_dispatch_counts() -> None:
    telemetry.global_ledger().clear()


def _backend(override: Optional[str]) -> str:
    return override or default_backend()


def _ledgered(fn):
    """Instrument a public kernel wrapper: one ledger entry (count + wall
    seconds, keyed by kernel name and resolved backend) per call in the
    process-global ledger and, under an active query trace, in the trace's
    own. Wall time is inclusive: wrappers that internally dispatch other
    wrappers (hash_build → radix_partition) tick both entries, exactly as
    the pre-§13 counters did.

    Under an active trace each call is also a ``telemetry.Dispatch`` span
    (id, parent dispatch, the trace's id), which ``tiling.pad`` and
    ``tiling.round_trip`` fill with padding bytes and device round trips,
    inside a ``jax.profiler.TraceAnnotation`` named ``barq.<kernel>``."""
    bidx = list(inspect.signature(fn).parameters).index("backend")
    name = fn.__name__
    label = tiling.ANNOTATION_PREFIX + name

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        be = kwargs.get("backend")
        if be is None and len(args) > bidx:
            be = args[bidx]
        be = be or default_backend()
        tr = telemetry.current_trace()
        if tr is None:
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                telemetry.record_dispatch(name, be, t0, time.perf_counter() - t0)
        d, token = telemetry.open_dispatch(tr, name, be)
        try:
            with tiling.annotation()(label):
                return fn(*args, **kwargs)
        finally:
            telemetry.close_dispatch(tr, d, token)

    return wrapper


# -- join_expand ---------------------------------------------------------------


@_ledgered
def join_expand(
    lstarts, llens, rstarts, rlens, cum, base: int, count: int,
    backend: Optional[str] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    be = _backend(backend)
    if be == "numpy":
        return vecops.expand_cross(lstarts, llens, rstarts, rlens, cum, base, count)
    if be == "jax":
        from repro.kernels import ref

        li, ri = ref.join_expand(lstarts, llens, rstarts, rlens, cum, base, count)
        return np.asarray(li), np.asarray(ri)
    if be == "pallas":
        from repro.kernels.join_expand import G_MAX, join_expand_pallas

        if len(lstarts) <= G_MAX:
            return join_expand_pallas(
                lstarts, llens, rstarts, rlens, cum, base, count,
                interpret=_interpret(),
            )
        # split oversized probes into group chunks
        lis, ris = [], []
        emitted = 0
        g0 = int(np.searchsorted(cum, base, side="right") - 1)
        while emitted < count:
            g1 = min(g0 + G_MAX, len(lstarts))
            chunk_cum = cum[g0 : g1 + 1]
            avail = int(chunk_cum[-1]) - (base + emitted)
            take = min(count - emitted, avail)
            li, ri = join_expand_pallas(
                lstarts[g0:g1],
                llens[g0:g1],
                rstarts[g0:g1],
                rlens[g0:g1],
                (chunk_cum - chunk_cum[0]).astype(np.int32),
                base + emitted - int(chunk_cum[0]),
                take,
                interpret=_interpret(),
            )
            lis.append(li)
            ris.append(ri)
            emitted += take
            g0 = g1
        return np.concatenate(lis), np.concatenate(ris)
    raise ValueError(be)


# -- gather_emit ---------------------------------------------------------------


@_ledgered
def gather_emit(
    lcols,
    rcols,
    li,
    ri,
    lsel=(),
    rsel=(),
    pairs=(),
    backend: Optional[str] = None,
    out: Optional[np.ndarray] = None,
    out_offset: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Fused join emission (see vecops.gather_emit for the contract):
    gather emitted rows through (li, ri), NULL-extend virtual right rows
    (ri == -1), and fold secondary-key equality ``pairs`` into the validity
    mask — one dispatch per output block instead of per column."""
    be = _backend(backend)
    lsel, rsel, pairs = tuple(lsel), tuple(rsel), tuple(pairs)
    if be == "numpy":
        return vecops.gather_emit(lcols, rcols, li, ri, lsel, rsel, pairs,
                                  out, out_offset)
    c = int(len(li))
    k = len(lsel) + len(rsel)
    lcols = np.ascontiguousarray(lcols, dtype=np.int32)
    # normalize a missing/empty right side to a 1-wide dummy addressed only
    # by virtual (-1) indices, so the jitted paths keep static shapes
    if rcols is None or rcols.shape[1] == 0:
        kr_src = 1 if rcols is None else max(int(rcols.shape[0]), 1)
        rcols_n = np.full((kr_src, 1), -1, dtype=np.int32)
        ri_n = np.full(c, -1, dtype=np.int32)
    else:
        rcols_n = np.ascontiguousarray(rcols, dtype=np.int32)
        ri_n = np.asarray(ri, dtype=np.int32)
    li_n = np.asarray(li, dtype=np.int32)

    if be == "jax":
        from repro.kernels import ref

        block, mask = ref.gather_emit(lcols, rcols_n, li_n, ri_n, lsel, rsel, pairs)
        block, mask = np.asarray(block), np.asarray(mask)
    elif be == "pallas":
        from repro.kernels.gather_emit import gather_emit_pallas

        # kernel layout: emitted rows first, pair rows at the source tails
        lrows = [max(r, 0) for r in lsel] + [lp for lp, _ in pairs]
        rrows = [max(r, 0) for r in rsel] + [rp for _, rp in pairs]
        lsrc = lcols[lrows] if lrows else np.zeros((1, max(lcols.shape[1], 1)), np.int32)
        rsrc = rcols_n[rrows] if rrows else np.zeros((1, rcols_n.shape[1]), np.int32)
        lout, rout, maski = gather_emit_pallas(lsrc, rsrc, li_n, ri_n, len(pairs),
                                               interpret=_interpret())
        block = np.concatenate([lout[: len(lsel)], rout[: len(rsel)]], axis=0)
        mask = maski.astype(bool)
    else:
        raise ValueError(be)

    if any(r < 0 for r in lsel + rsel) and not block.flags.writeable:
        block = block.copy()  # jit outputs are read-only
    for j, row in enumerate(lsel):  # -1 emit rows = NULL columns
        if row < 0:
            block[j] = -1
    for j, row in enumerate(rsel):
        if row < 0:
            block[len(lsel) + j] = -1
    if out is not None:
        view = out[:k, out_offset : out_offset + c]
        view[...] = block
        return view, mask
    return block, mask


# -- sorted_search ---------------------------------------------------------------


@_ledgered
def sorted_search(keys, queries, side: str = "left", backend: Optional[str] = None):
    be = _backend(backend)
    if be == "numpy":
        return vecops.sorted_search(keys, queries, side)
    if be == "jax":
        from repro.kernels import ref

        return np.asarray(ref.sorted_search(keys, queries, side))
    if be == "pallas":
        from repro.kernels.sorted_search import sorted_search_pallas

        return sorted_search_pallas(keys, queries, side, interpret=_interpret())
    raise ValueError(be)


# -- frontier_dedup ---------------------------------------------------------------


@_ledgered
def frontier_dedup(
    cand_hi, cand_lo, vis_hi, vis_lo, backend: Optional[str] = None
) -> np.ndarray:
    """Delta-frontier mask for one property-path BFS round: keep each
    lexicographically sorted (source, node) candidate pair iff it is the
    first occurrence in the batch and absent from the sorted visited set
    (see vecops.frontier_dedup)."""
    be = _backend(backend)
    if be == "numpy":
        return vecops.frontier_dedup(cand_hi, cand_lo, vis_hi, vis_lo)
    cand_hi = np.asarray(cand_hi, dtype=np.int32)
    cand_lo = np.asarray(cand_lo, dtype=np.int32)
    vis_hi = np.asarray(vis_hi, dtype=np.int32)
    vis_lo = np.asarray(vis_lo, dtype=np.int32)
    if be == "jax":
        from repro.kernels import ref

        return np.asarray(ref.frontier_dedup(cand_hi, cand_lo, vis_hi, vis_lo))
    if be == "pallas":
        from repro.kernels.frontier_dedup import frontier_dedup_pallas

        return frontier_dedup_pallas(cand_hi, cand_lo, vis_hi, vis_lo,
                                     interpret=_interpret())
    raise ValueError(be)


# -- segment aggregation ---------------------------------------------------------------


@_ledgered
def segment_reduce(keys, values, func: str, backend: Optional[str] = None,
                   seg=None):
    """(run_keys, per-run aggregates) over sorted keys. ``seg`` is the
    optional precomputed (run_keys, lengths, seg_ids) of the key column
    (see vecops.segment_reduce); the scan backends derive boundaries
    in-kernel and ignore it."""
    be = _backend(backend)
    if be == "numpy":
        return vecops.segment_reduce(keys, values, func, seg)
    # jax / pallas: segmented scan then pick run ends
    keys = np.asarray(keys)
    n = len(keys)
    if n == 0:
        return keys.astype(np.int32), np.zeros(0, dtype=np.float64)
    vals = (
        np.ones(n, dtype=np.float32)
        if func == "count" or values is None
        else np.asarray(values, dtype=np.float32)
    )
    op = "sum" if func == "count" else func
    if be == "jax":
        from repro.kernels import ref

        scan = np.asarray(ref.segment_scan(keys, vals, op))
    elif be == "pallas":
        from repro.kernels.segment_reduce import segment_scan_pallas

        scan = segment_scan_pallas(keys, vals, op, interpret=_interpret())
    else:
        raise ValueError(be)
    run_end = np.empty(n, dtype=bool)
    run_end[-1] = True
    run_end[:-1] = keys[1:] != keys[:-1]
    return keys[run_end].astype(np.int32), scan[run_end].astype(np.float64)


# -- expression VM (DESIGN.md §9) -------------------------------------------


@_ledgered
def expr_eval(prog, icols, fcols, backend: Optional[str] = None):
    """Evaluate a compiled ExprProgram over an input block: (value, error)
    numpy arrays for the output register. The numpy path is the float64
    oracle; jax runs the jit'd float32 reference; pallas runs the fused
    kernel (whole program, one dispatch per batch)."""
    be = _backend(backend)
    icols = np.ascontiguousarray(icols, dtype=np.int32)
    if be == "numpy":
        from repro.core.exprs.vm import _interp

        val, err = _interp(np, prog, icols, np.asarray(fcols, np.float64),
                           np.float64)
        return np.asarray(val), np.asarray(err)
    fcols = np.ascontiguousarray(fcols, dtype=np.float32)
    if be == "jax":
        from repro.kernels import ref

        val, err = ref.expr_eval(icols, fcols, prog)
        return np.asarray(val), np.asarray(err)
    if be == "pallas":
        from repro.kernels.expr_eval import expr_eval_pallas

        return expr_eval_pallas(icols, fcols, prog, interpret=_interpret())
    raise ValueError(be)


# -- radix partition ---------------------------------------------------------------


@_ledgered
def radix_partition(keys, n_parts: int, backend: Optional[str] = None):
    be = _backend(backend)
    if be == "numpy":
        pid = vecops.hash_partition(np.asarray(keys), n_parts)
        return pid, vecops.partition_histogram(pid, n_parts)
    if be == "jax":
        from repro.kernels import ref

        pid, hist = ref.radix_partition(keys, n_parts)
        return np.asarray(pid), np.asarray(hist)
    if be == "pallas":
        from repro.kernels.radix_partition import radix_partition_pallas

        return radix_partition_pallas(keys, n_parts, interpret=_interpret())
    raise ValueError(be)


# -- hash join: build / probe (DESIGN.md §11) --------------------------------------
#
# The join key is an int32 (hi, lo) pair compared lexicographically;
# single-variable keys pass key_hi=None (see vecops §11 header). The build
# step reuses the radix_partition kernel for bucketing (its dispatch is
# counted separately), then reorders rows by (partition, key) — an XLA/host
# sort; sorting inside Pallas is not profitable on TPU. The probe step is
# where the Pallas path runs its own kernel (gather-free counting search).


@_ledgered
def hash_build(
    key_hi, key_lo, n_parts: int, backend: Optional[str] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Partitioned build layout for ``hash_probe``: returns
    (order, part_starts) where ``order`` permutes build rows into
    partition-grouped, key-sorted position and ``part_starts`` is the
    (P+1,) prefix-sum of the partition histogram."""
    be = _backend(backend)
    key_lo = np.asarray(key_lo, dtype=np.int32)
    mixed = vecops.mix_pair(key_hi, key_lo)
    pid, hist = radix_partition(mixed, n_parts, backend=be)
    part_starts = np.concatenate(
        [np.zeros(1, np.int32), np.cumsum(hist, dtype=np.int64)]
    ).astype(np.int32)
    if be == "numpy":
        order = vecops.hash_build_order(pid, key_hi, key_lo, n_parts)
    elif be in ("jax", "pallas"):
        from repro.kernels import ref

        hi = (
            np.zeros(len(key_lo), np.int32)
            if key_hi is None
            else np.asarray(key_hi, np.int32)
        )
        # bucketed like the kernels; padding rows sort after every real one
        n = len(key_lo)
        order = tiling.round_trip(
            ref.hash_build_order,
            tiling.pad(pid, tiling.SORT_TILE, np.iinfo(np.int32).max),
            tiling.pad(hi, tiling.SORT_TILE, 0),
            tiling.pad(key_lo, tiling.SORT_TILE, 0),
        )[:n]
    else:
        raise ValueError(be)
    return order, part_starts


@_ledgered
def hash_probe(
    spid,
    skey_hi,
    skey_lo,
    qkey_hi,
    qkey_lo,
    part_starts,
    n_parts: int,
    backend: Optional[str] = None,
    cache: Optional[dict] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """(lo, hi) match-run boundaries of each probe key in a hash_build
    layout: build rows [lo[i], hi[i]) carry probe i's exact key. ``spid``
    is the partition id per *reordered* build row (repeat of arange over
    the histogram). ``cache`` is a per-build dict the operator threads
    through consecutive probe batches so build-side derivations (the
    global composite) are computed once, not per batch."""
    be = _backend(backend)
    skey_lo = np.asarray(skey_lo, dtype=np.int32)
    qkey_lo = np.asarray(qkey_lo, dtype=np.int32)
    if len(skey_lo) == 0 or len(qkey_lo) == 0:
        z = np.zeros(len(qkey_lo), np.int32)
        return z, z.copy()
    qpid = vecops.hash_partition(vecops.mix_pair(qkey_hi, qkey_lo), n_parts)
    if be == "numpy":
        return vecops.hash_probe_positions(
            spid, skey_hi, skey_lo, qpid, qkey_hi, qkey_lo, part_starts,
            cache=cache,
        )
    z_s = np.zeros(len(skey_lo), np.int32)
    z_q = np.zeros(len(qkey_lo), np.int32)
    shi = z_s if skey_hi is None else np.asarray(skey_hi, np.int32)
    qhi = z_q if qkey_hi is None else np.asarray(qkey_hi, np.int32)
    if be == "jax":
        from repro.kernels import ref

        lo = ref.hash_probe(spid, shi, skey_lo, qpid, qhi, qkey_lo,
                            part_starts, side="left")
        hi = ref.hash_probe(spid, shi, skey_lo, qpid, qhi, qkey_lo,
                            part_starts, side="right")
        return np.asarray(lo), np.asarray(hi)
    if be == "pallas":
        from repro.kernels.hash_join import hash_probe_pallas

        return hash_probe_pallas(spid, shi, skey_lo, qpid, qhi, qkey_lo,
                                 interpret=_interpret())
    raise ValueError(be)


# -- bloom filter: SIP prefilters (DESIGN.md §12) ----------------------------------


@_ledgered
def bloom_build(
    keys, n_words: Optional[int] = None, backend: Optional[str] = None
) -> Tuple[np.ndarray, int, int]:
    """(words, lo, hi): blocked bloom filter words (uint32) plus the
    min/max code range of the build keys — the payload of a SipFilter.
    ``n_words`` defaults to vecops.bloom_n_words(len(keys))."""
    be = _backend(backend)
    keys = np.ascontiguousarray(keys, dtype=np.int32)
    if n_words is None:
        n_words = vecops.bloom_n_words(len(keys))
    if be == "numpy" or len(keys) == 0:
        return vecops.bloom_build(keys, n_words)
    lo, hi = int(keys.min()), int(keys.max())
    if be == "jax":
        from repro.kernels import ref

        return np.asarray(ref.bloom_build(keys, n_words)), lo, hi
    if be == "pallas":
        from repro.kernels.bloom_filter import bloom_build_pallas

        return bloom_build_pallas(keys, n_words, interpret=_interpret()), lo, hi
    raise ValueError(be)


@_ledgered
def bloom_probe(words, queries, backend: Optional[str] = None) -> np.ndarray:
    """(C,) bool membership mask over ``queries`` — no false negatives."""
    be = _backend(backend)
    queries = np.ascontiguousarray(queries, dtype=np.int32)
    if be == "numpy":
        return vecops.bloom_probe(words, queries)
    if len(queries) == 0:
        return np.zeros(0, dtype=bool)
    if be == "jax":
        from repro.kernels import ref

        return np.asarray(ref.bloom_probe(words, queries))
    if be == "pallas":
        from repro.kernels.bloom_filter import bloom_probe_pallas

        return bloom_probe_pallas(words, queries, interpret=_interpret())
    raise ValueError(be)
