"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) and jnp-ref
backends against the numpy oracle (repro.core.vecops)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import vecops
from repro.kernels import ops

BACKENDS = ("jax", "pallas")


def _groups(rng, g, max_l, max_r):
    llens = rng.randint(1, max_l + 1, g).astype(np.int32)
    rlens = rng.randint(1, max_r + 1, g).astype(np.int32)
    lstarts = np.cumsum(np.concatenate([[0], llens[:-1]])).astype(np.int32)
    rstarts = np.cumsum(np.concatenate([[0], rlens[:-1]])).astype(np.int32)
    cum = vecops.group_output_offsets(llens, rlens)
    return lstarts, llens, rstarts, rlens, cum


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("g,max_l,max_r,base", [
    (1, 1, 1, 0),
    (7, 3, 5, 2),
    (64, 8, 8, 11),
    (513, 4, 2, 0),      # > one grid block of groups
    (37, 40, 1, 5),      # long left runs
    (37, 1, 40, 5),      # long right runs
])
def test_join_expand_sweep(backend, g, max_l, max_r, base):
    rng = np.random.RandomState(g * 7 + max_l)
    ls, ll, rs, rl, cum = _groups(rng, g, max_l, max_r)
    total = int(cum[-1])
    count = total - base
    want = vecops.expand_cross(ls, ll, rs, rl, cum, base, count)
    got = ops.join_expand(ls, ll, rs, rl, cum.astype(np.int32), base, count,
                          backend=backend)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_join_expand_group_chunking():
    """Pallas wrapper must split probes beyond G_MAX groups."""
    from repro.kernels.join_expand import G_MAX

    rng = np.random.RandomState(0)
    g = G_MAX + 77
    ls, ll, rs, rl, cum = _groups(rng, g, 2, 2)
    total = int(cum[-1])
    want = vecops.expand_cross(ls, ll, rs, rl, cum, 3, total - 3)
    got = ops.join_expand(ls, ll, rs, rl, cum.astype(np.int64), 3, total - 3,
                          backend="pallas")
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def _ge_case(rng, kl, kr, nl, nr, c, virtual_frac):
    lcols = rng.randint(0, 40, (kl, nl)).astype(np.int32)
    rcols = rng.randint(0, 40, (kr, max(nr, 1))).astype(np.int32)
    li = rng.randint(0, nl, c).astype(np.int32)
    if nr == 0:
        ri = np.full(c, -1, np.int32)
    else:
        ri = rng.randint(0, nr, c).astype(np.int32)
        ri[rng.rand(c) < virtual_frac] = -1
    return lcols, rcols[:, :nr] if nr else rcols[:, :0], li, ri


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kl,kr,nl,nr,c,vf", [
    (1, 1, 1, 1, 1, 0.0),
    (2, 2, 50, 30, 100, 0.0),
    (3, 4, 700, 300, 1000, 0.25),     # virtual rows
    (2, 2, 1500, 2000, 600, 0.1),     # > one source chunk
    (4, 1, 64, 64, 5000, 0.0),        # long output
])
def test_gather_emit_sweep(backend, kl, kr, nl, nr, c, vf):
    rng = np.random.RandomState(kl * 31 + nl + c)
    lcols, rcols, li, ri = _ge_case(rng, kl, kr, nl, nr, c, vf)
    lsel = tuple(range(kl))
    rsel = tuple(range(kr))[:1]
    pairs = ((kl - 1, kr - 1),)
    want = vecops.gather_emit(lcols, rcols, li, ri, lsel, rsel, pairs)
    got = ops.gather_emit(lcols, rcols, li, ri, lsel, rsel, pairs, backend=backend)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("backend", BACKENDS)
def test_gather_emit_mask_only_and_null_rows(backend):
    """semi/anti use the primitive mask-only (no emitted columns); concat
    uses -1 lsel rows for NULL schema alignment."""
    rng = np.random.RandomState(7)
    lcols, rcols, li, ri = _ge_case(rng, 3, 3, 80, 60, 200, 0.2)
    pairs = ((0, 0), (2, 1))
    want = vecops.gather_emit(lcols, rcols, li, ri, (), (), pairs)
    got = ops.gather_emit(lcols, rcols, li, ri, (), (), pairs, backend=backend)
    assert got[0].shape == (0, 200)
    np.testing.assert_array_equal(got[1], want[1])

    wb, _ = vecops.gather_emit(lcols, None, li, None, (0, -1, 2), (), ())
    gb, _ = ops.gather_emit(lcols, None, li, None, (0, -1, 2), (), (),
                            backend=backend)
    assert (wb[1] == -1).all()
    np.testing.assert_array_equal(gb, wb)


def test_gather_emit_out_offset():
    """The pooled fast path writes into the destination at an offset."""
    rng = np.random.RandomState(3)
    lcols, rcols, li, ri = _ge_case(rng, 2, 2, 50, 50, 64, 0.0)
    want, _ = vecops.gather_emit(lcols, rcols, li, ri, (0, 1), (0,), ())
    out = np.full((3, 300), 99, np.int32)
    vecops.gather_emit(lcols, rcols, li, ri, (0, 1), (0,), (),
                       out=out, out_offset=100)
    np.testing.assert_array_equal(out[:, 100:164], want)
    assert (out[:, :100] == 99).all() and (out[:, 164:] == 99).all()


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_gather_emit_property(data):
    """Random shapes/selections: every backend matches the numpy oracle."""
    rng = np.random.RandomState(data.draw(st.integers(0, 10**6)))
    kl = data.draw(st.integers(1, 4))
    kr = data.draw(st.integers(1, 4))
    nl = data.draw(st.integers(1, 600))
    nr = data.draw(st.integers(0, 600))
    c = data.draw(st.integers(1, 700))
    lcols, rcols, li, ri = _ge_case(rng, kl, kr, nl, nr, c, 0.15)
    lsel = tuple(
        data.draw(st.integers(-1, kl - 1)) for _ in range(data.draw(st.integers(0, kl)))
    )
    rsel = tuple(range(data.draw(st.integers(0, kr))))
    pairs = tuple(
        (data.draw(st.integers(0, kl - 1)), data.draw(st.integers(0, kr - 1)))
        for _ in range(data.draw(st.integers(0, 2)))
    )
    want = vecops.gather_emit(lcols, rcols, li, ri, lsel, rsel, pairs)
    for backend in BACKENDS:
        got = ops.gather_emit(lcols, rcols, li, ri, lsel, rsel, pairs,
                              backend=backend)
        np.testing.assert_array_equal(got[0], want[0], err_msg=backend)
        np.testing.assert_array_equal(got[1], want[1], err_msg=backend)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n,m", [(0, 5), (1, 1), (100, 37), (5000, 700)])
@pytest.mark.parametrize("side", ["left", "right"])
def test_sorted_search_sweep(backend, n, m, side):
    rng = np.random.RandomState(n + m)
    keys = np.sort(rng.randint(-50, 50, n)).astype(np.int32)
    qs = rng.randint(-60, 60, m).astype(np.int32)
    want = vecops.sorted_search(keys, qs, side)
    got = ops.sorted_search(keys, qs, side, backend=backend)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("func", ["count", "sum", "min", "max"])
@pytest.mark.parametrize("n,k", [(1, 1), (100, 5), (3000, 40), (2048, 1)])
def test_segment_reduce_sweep(backend, func, n, k):
    rng = np.random.RandomState(n * 3 + k)
    keys = np.sort(rng.randint(0, k, n)).astype(np.int32)
    vals = rng.randn(n)
    want_k, want_v = vecops.segment_reduce(keys, vals, func)
    got_k, got_v = ops.segment_reduce(keys, vals, func, backend=backend)
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_filter_conjunction_compiles_to_vm(backend):
    """The old conjunction-kernel spec format — (col, op, rhs_col|-1,
    const) conjunctions over int columns — is now a *compile target* of
    the expression VM: the equivalent And-of-Cmp tree must produce the
    plain numpy conjunction mask through every backend (the fused
    expr_eval kernel replaced kernels/filter_eval.py)."""
    from repro.core import algebra as A
    from repro.core.batch import ColumnBatch
    from repro.core.dictionary import Dictionary
    from repro.core.exprs import compile_expr, eval_program_mask

    ops_names = ("=", "!=", "<", "<=", ">", ">=")
    rng = np.random.RandomState(0)
    d = Dictionary()
    for v in range(20):  # term i == int i -> code i: codes ARE the values
        d.encode(int(v))
    for k, n in [(1, 1), (3, 100), (6, 5000)]:
        cols = rng.randint(0, 20, (k, n)).astype(np.int32)
        spec = tuple(
            (rng.randint(k), rng.randint(6),
             rng.randint(k) if rng.rand() < 0.5 else -1, int(rng.randint(0, 20)))
            for _ in range(min(k, 3))
        )
        want = np.ones(n, dtype=bool)
        terms = []
        for col, op, rhs_col, const in spec:
            a = cols[col]
            b = cols[rhs_col] if rhs_col >= 0 else np.int32(const)
            want &= [a == b, a != b, a < b, a <= b, a > b, a >= b][op]
            rhs = A.VarRef(rhs_col) if rhs_col >= 0 else A.Lit(const)
            terms.append(A.Cmp(ops_names[op], A.VarRef(col), rhs))
        expr = terms[0] if len(terms) == 1 else A.And(tuple(terms))
        batch = ColumnBatch.from_columns(
            tuple(range(k)), list(cols), capacity=max(n, 1)
        )
        prog = compile_expr(expr, d, "mask")
        got = eval_program_mask(prog, batch, d, backend=backend)[:n]
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n_parts", [2, 16, 128])
@pytest.mark.parametrize("n", [1, 500, 6000])
def test_radix_partition_sweep(backend, n_parts, n):
    rng = np.random.RandomState(n + n_parts)
    keys = rng.randint(0, 2**30, n).astype(np.int32)
    want_p, want_h = ops.radix_partition(keys, n_parts, backend="numpy")
    got_p, got_h = ops.radix_partition(keys, n_parts, backend=backend)
    np.testing.assert_array_equal(got_p, want_p)
    np.testing.assert_array_equal(got_h, want_h)


def _sorted_pairs(rng, n, hi_range, lo_range):
    hi = rng.randint(0, hi_range, n).astype(np.int32)
    lo = rng.randint(0, lo_range, n).astype(np.int32)
    order = np.lexsort((lo, hi))
    return hi[order], lo[order]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("c,v", [
    (0, 0),
    (1, 0),
    (1, 1),
    (100, 40),       # heavy duplication + visited overlap
    (700, 2500),     # > one cand block and > one visited tile
    (5000, 0),       # pure sort-unique (relation dedup path)
])
def test_frontier_dedup_sweep(backend, c, v):
    rng = np.random.RandomState(c * 13 + v + 1)
    ch, cl = _sorted_pairs(rng, c, 20, 20)
    vh, vl = _sorted_pairs(rng, v, 20, 20)
    if v:  # visited sets hold unique pairs
        keep = vecops.frontier_dedup(vh, vl, vh[:0], vl[:0])
        vh, vl = vh[keep], vl[keep]
    want = vecops.frontier_dedup(ch, cl, vh, vl)
    got = ops.frontier_dedup(ch, cl, vh, vl, backend=backend)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_frontier_dedup_property(data):
    """Masked candidates == set difference of unique pairs vs visited, on
    every backend."""
    rng = np.random.RandomState(data.draw(st.integers(0, 10**6)))
    c = data.draw(st.integers(0, 300))
    v = data.draw(st.integers(0, 300))
    ch, cl = _sorted_pairs(rng, c, 12, 12)
    vh, vl = _sorted_pairs(rng, v, 12, 12)
    if v:
        keep = vecops.frontier_dedup(vh, vl, vh[:0], vl[:0])
        vh, vl = vh[keep], vl[keep]
    want_set = set(zip(ch.tolist(), cl.tolist())) - set(
        zip(vh.tolist(), vl.tolist())
    )
    for backend in ("numpy",) + BACKENDS:
        mask = ops.frontier_dedup(ch, cl, vh, vl, backend=backend)
        got = set(zip(ch[mask].tolist(), cl[mask].tolist()))
        assert got == want_set, backend
        # first-occurrence semantics: masked rows are unique
        assert len(got) == int(mask.sum()), backend


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 30), min_size=1, max_size=300),
       st.sampled_from(["sum", "min", "max"]))
def test_segment_scan_property(keys, op):
    """Pallas segmented scan == per-run numpy reduce at run ends."""
    keys = np.sort(np.asarray(keys, np.int32))
    vals = np.random.RandomState(1).randn(len(keys))
    got_k, got_v = ops.segment_reduce(keys, vals, op, backend="pallas")
    want_k, want_v = vecops.segment_reduce(keys, vals, op)
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-4, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(
    st.lists(st.integers(-1000, 1000), min_size=1, max_size=400),
    st.lists(st.integers(-1100, 1100), min_size=1, max_size=200),
)
def test_sorted_search_property(keys, queries):
    """Positions returned by every backend partition the key array exactly
    like numpy searchsorted, for arbitrary (incl. negative) key spaces."""
    keys = np.sort(np.asarray(keys, np.int32))
    qs = np.asarray(queries, np.int32)
    for side in ("left", "right"):
        want = np.searchsorted(keys, qs, side=side)
        for backend in BACKENDS:
            got = ops.sorted_search(keys, qs, side, backend=backend)
            np.testing.assert_array_equal(got, want)


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_join_expand_property(data):
    """Random group structures: all backends emit the exact cross-product
    index sequence for every (base, count) window."""
    g = data.draw(st.integers(1, 50))
    rng = np.random.RandomState(g)
    ls, ll, rs, rl, cum = _groups(rng, g, 6, 6)
    total = int(cum[-1])
    base = data.draw(st.integers(0, max(total - 1, 0)))
    count = data.draw(st.integers(1, total - base))
    want = vecops.expand_cross(ls, ll, rs, rl, cum, base, count)
    for backend in BACKENDS:
        got = ops.join_expand(ls, ll, rs, rl, cum.astype(np.int32), base,
                              count, backend=backend)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])


def _grid_case(kernel, rng):
    """(numpy outputs, pallas outputs) of one kernel at sizes that span
    more than one block on both axes of its grid."""
    if kernel == "sorted_search":
        keys = np.sort(rng.randint(-500, 500, 5000)).astype(np.int32)
        qs = rng.randint(-600, 600, 3000).astype(np.int32)
        return [(ops.sorted_search(keys, qs, "right", backend=be),)
                for be in ("numpy", "pallas")]
    if kernel == "hash_probe":
        bk = rng.randint(-1, 700, 5000).astype(np.int32)
        qk = rng.randint(-1, 703, 3000).astype(np.int32)
        out = []
        for be in ("numpy", "pallas"):
            order, starts = ops.hash_build(None, bk, 16, backend=be)
            spid = np.repeat(np.arange(16, dtype=np.int32), np.diff(starts))
            out.append(ops.hash_probe(spid, None, bk[order], None, qk, starts,
                                      16, backend=be))
        return out
    if kernel == "gather_emit":
        lcols, rcols, li, ri = _ge_case(rng, 3, 2, 2500, 1800, 3000, 0.1)
        return [ops.gather_emit(lcols, rcols, li, ri, (0, 1, 2), (0,),
                                ((2, 1),), backend=be)
                for be in ("numpy", "pallas")]
    if kernel == "frontier_dedup":
        ch, cl = _sorted_pairs(rng, 3000, 60, 60)
        vh, vl = _sorted_pairs(rng, 5000, 60, 60)
        keep = vecops.frontier_dedup(vh, vl, vh[:0], vl[:0])
        vh, vl = vh[keep], vl[keep]
        return [(ops.frontier_dedup(ch, cl, vh, vl, backend=be),)
                for be in ("numpy", "pallas")]
    if kernel == "bloom":
        keys = rng.randint(0, 1 << 20, 3000).astype(np.int32)
        qs = rng.randint(0, 1 << 20, 3000).astype(np.int32)
        out = []
        for be in ("numpy", "pallas"):
            words, _, _ = ops.bloom_build(keys, n_words=2048, backend=be)
            out.append((words, ops.bloom_probe(words, qs, backend=be)))
        return out
    raise ValueError(kernel)


@pytest.mark.parametrize("kernel", [
    "sorted_search", "hash_probe", "gather_emit", "frontier_dedup", "bloom",
])
def test_pallas_multi_block_grid(kernel):
    """Every accumulating kernel at more than one block on both grid axes.
    Off the TPU, Pallas runs in the TPU interpreter, which refuses a grid
    that leaves an output block and comes back to it: the chip writes an
    output block back when the grid moves on and never reads it in again,
    so such a kernel passes the plain interpreter and miscounts on the
    chip."""
    want, got = _grid_case(kernel, np.random.RandomState(11))
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
