"""Sideways information passing (DESIGN.md §12): bloom kernel parity
across backends, SipFilter semantics, planner annotations + bushy
ordering, engine equivalence with SIP on/off, and the serve-layer plan
cache fingerprint."""

import hashlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Engine, EngineConfig, QuadStore
from repro.core import planner as PL
from repro.core import vecops
from repro.core.batch import NULL_ID
from repro.core.operators import scan as scan_mod
from repro.core.operators.scan import IndexScan
from repro.core.adaptive import AdaptiveBatchSizer
from repro.core.algebra import K, TriplePattern, V
from repro.core.sip import SipFilter
from repro.kernels import ops

BACKENDS = ("numpy", "jax", "pallas")


# ---------------------------------------------------------------------------
# bloom kernel: three-backend parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_bloom_empty_build(backend):
    words, lo, hi = ops.bloom_build(np.zeros(0, np.int32), backend=backend)
    assert hi < lo  # provably-empty marker
    q = np.arange(10, dtype=np.int32)
    assert not ops.bloom_probe(words, q, backend=backend).any()


@pytest.mark.parametrize("backend", BACKENDS)
def test_bloom_no_false_negatives_and_hits(backend):
    rng = np.random.RandomState(5)
    keys = rng.randint(0, 1 << 18, size=777).astype(np.int32)
    words, lo, hi = ops.bloom_build(keys, backend=backend)
    assert lo == int(keys.min()) and hi == int(keys.max())
    # every inserted key must probe positive (no false negatives)
    assert ops.bloom_probe(words, keys, backend=backend).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_bloom_all_miss(backend):
    keys = np.arange(100, dtype=np.int32)
    words, _, _ = ops.bloom_build(keys, backend=backend)
    misses = np.arange(1 << 20, (1 << 20) + 500, dtype=np.int32)
    hits = ops.bloom_probe(words, misses, backend=backend)
    # disjoint domain: only bloom false positives may fire, and with
    # ~16 bits/key they must be rare
    assert hits.mean() < 0.05


@pytest.mark.parametrize("backend", BACKENDS)
def test_bloom_null_id_key(backend):
    """NULL_ID (-1) is a legal join key (it equals itself in joins) and
    must round-trip through the uint32 hash on every backend."""
    keys = np.asarray([NULL_ID, 3, 7], dtype=np.int32)
    words, lo, hi = ops.bloom_build(keys, backend=backend)
    assert lo == NULL_ID and hi == 7
    got = ops.bloom_probe(
        words, np.asarray([NULL_ID, 3, 7], dtype=np.int32), backend=backend
    )
    assert got.all()


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_bloom_backend_parity_property(data):
    """Random key/query sets (including >16-bit domains): jax and pallas
    are bit-identical to the numpy oracle."""
    rng = np.random.RandomState(data.draw(st.integers(0, 10**6)))
    nk = data.draw(st.integers(0, 800))
    nq = data.draw(st.integers(0, 900))
    dom = data.draw(st.sampled_from([64, 1 << 10, 1 << 17, 1 << 22]))
    keys = rng.randint(-2, dom, size=nk).astype(np.int32)
    queries = rng.randint(-2, dom, size=nq).astype(np.int32)
    w0, lo0, hi0 = ops.bloom_build(keys, backend="numpy")
    m0 = ops.bloom_probe(w0, queries, backend="numpy")
    for backend in ("jax", "pallas"):
        w, lo, hi = ops.bloom_build(keys, backend=backend)
        np.testing.assert_array_equal(w, w0)
        assert (lo, hi) == (lo0, hi0)
        np.testing.assert_array_equal(
            ops.bloom_probe(w, queries, backend=backend), m0
        )
    if nk:
        members = np.isin(queries, keys)
        assert (m0 | ~members).all()  # no false negatives


def test_bloom_pallas_dispatch_counted():
    before_b = ops.dispatch_count("bloom_build")
    before_p = ops.dispatch_count("bloom_probe")
    keys = np.arange(100, dtype=np.int32)
    words, _, _ = ops.bloom_build(keys, backend="pallas")
    ops.bloom_probe(words, keys, backend="pallas")
    assert ops.dispatch_count("bloom_build") == before_b + 1
    assert ops.dispatch_count("bloom_probe") == before_p + 1


def test_bloom_n_words_sizing():
    assert vecops.bloom_n_words(0) >= 1
    for n in (1, 100, 10_000):
        w = vecops.bloom_n_words(n)
        assert w & (w - 1) == 0  # power of two
    assert vecops.bloom_n_words(10**9) <= 1 << 20  # capped


# ---------------------------------------------------------------------------
# SipFilter runtime semantics
# ---------------------------------------------------------------------------


def test_sip_filter_pass_through_without_provider():
    f = SipFilter(var=0)
    assert f.code_range() is None
    assert f.mask(np.arange(5, dtype=np.int32)) is None


def test_sip_filter_range_and_mask():
    f = SipFilter(var=0)
    f.bind(lambda: ("keys", np.asarray([10, 20, 30], np.int32)))
    assert f.code_range() == (10, 30)
    m = f.mask(np.asarray([5, 10, 20, 25, 30, 99], np.int32))
    assert m[1] and m[2] and m[4]  # members always kept
    assert not m[0] and not m[5]  # outside the range: always pruned
    assert f.rows_pruned >= 2


def test_sip_filter_empty_build_prunes_everything():
    f = SipFilter(var=0)
    f.bind(lambda: ("keys", np.zeros(0, np.int32)))
    lo, hi = f.code_range()
    assert hi < lo
    assert not f.mask(np.arange(100, dtype=np.int32)).any()


@pytest.mark.parametrize("distinct", [3, 20])
def test_sip_filter_bloom_sized_by_build_rows(distinct):
    """The bloom's word count, a compiled shape on a device plane, follows
    the build side's rows, not its distinct keys, so two requests of one
    template with 20-row build sides share one program."""
    keys = np.arange(20, dtype=np.int32) % distinct
    f = SipFilter(var=0)
    f.bind(lambda: ("keys", keys))
    f.mask(keys)
    assert len(f.keys) == distinct
    assert len(f.words) == vecops.bloom_n_words(20)


def test_sip_filter_range_only_provider():
    f = SipFilter(var=0)
    f.bind(lambda: ("range", 5, 9))
    assert f.code_range() == (5, 9)
    m = f.mask(np.asarray([4, 5, 9, 10], np.int32))
    assert list(m) == [False, True, True, False]


# ---------------------------------------------------------------------------
# scan integration: can_skip + mask-mode fallback
# ---------------------------------------------------------------------------


def _scan_store():
    store = QuadStore()
    for i in range(200):
        store.add(f":s{i:03d}", ":p", f":o{i % 7}")
    return store.build()


def test_scan_skip_on_unsorted_var_still_raises():
    store = _scan_store()
    pat = TriplePattern(V(0), K(":p"), V(1))
    scan = IndexScan(store, pat)
    sv = scan.sorted_by()
    other = [v for v in scan.var_ids() if v != sv][0]
    assert scan.can_skip(sv)
    assert not scan.can_skip(other)
    with pytest.raises(ValueError):
        scan.skip(other, 3)


def test_scan_sip_falls_back_to_mask_on_unsorted_var():
    """A SIP filter on a non-sorted var must not try to seek (would
    raise); it degrades to batch masking via can_skip, no exceptions."""
    store = _scan_store()
    pat = TriplePattern(V(0), K(":p"), V(1))
    probe = IndexScan(store, pat)
    ov = [v for v in probe.var_ids() if v != probe.sorted_by()][0]
    # collect the unsorted var's values without any filter, pick two
    all_vals = []
    while True:
        b = probe.next_batch()
        if b is None:
            break
        all_vals.append(b.columns[b.col_index(ov), : b.n_rows][b.mask[: b.n_rows]])
        b.release()
    all_vals = np.concatenate(all_vals)
    keep = np.unique(all_vals)[:2].astype(np.int32)
    expected = int(np.isin(all_vals, keep).sum())
    assert expected > 0
    f = SipFilter(var=ov)
    f.bind(lambda: ("keys", keep))
    scan = IndexScan(store, pat, sip_filters=[f])
    rows = 0
    while True:
        b = scan.next_batch()
        if b is None:
            break
        vals = b.columns[b.col_index(ov), : b.n_rows][b.mask[: b.n_rows]]
        assert np.isin(vals, keep).all()
        rows += b.n_active
        b.release()
    assert rows == expected
    assert f.rows_pruned > 0


def test_scan_sip_range_narrowing_cuts_reads():
    """On the sorted var the filter seeks: rows_scanned must shrink to
    roughly the build-side range instead of the whole relation."""
    store = _scan_store()
    pat = TriplePattern(V(0), K(":p"), V(1))
    base = IndexScan(store, pat, want_sorted_var=0)
    assert base.sorted_by() == 0  # subject-sorted (SPO-family index)
    lo = store.dict.lookup(":s050")
    hi = store.dict.lookup(":s059")
    f = SipFilter(var=0)
    f.bind(lambda: ("range", min(lo, hi), max(lo, hi)))
    scan = IndexScan(store, pat, want_sorted_var=0, sip_filters=[f])
    n = 0
    while True:
        b = scan.next_batch()
        if b is None:
            break
        n += b.n_active
        b.release()
    assert n <= 10
    assert scan.stats.rows_scanned < 200
    assert scan.stats.extra.get("sip_range_seeks", 0) == 1


# ---------------------------------------------------------------------------
# scan integration: seeks straight to the build keys' runs
# ---------------------------------------------------------------------------


def _seek_store():
    """1,000 subjects of :p with three objects each (3,000 rows sorted by
    subject)."""
    store = QuadStore()
    for i in range(1000):
        for j in range(3):
            store.add(f":s{i:04d}", ":p", f":o{(i + j) % 11}")
    return store.build()


def _codes(store, names):
    return np.asarray([store.dict.lookup(n) for n in names], np.int32)


def _keys_filter(keys, var=0):
    f = SipFilter(var=var)
    f.bind(lambda: ("keys", np.asarray(keys, np.int32)))
    return f


def _drain(scan):
    """Active rows as (var -> column) over the whole scan."""
    out = {v: [] for v in scan.var_ids()}
    while True:
        b = scan.next_batch()
        if b is None:
            break
        live = b.mask[: b.n_rows]
        for v in out:
            out[v].append(b.columns[b.col_index(v), : b.n_rows][live])
        b.release()
    return {v: np.concatenate(c) if c else np.zeros(0, np.int32) for v, c in out.items()}


P_SO = TriplePattern(V(0), K(":p"), V(1))


def _sip_scan(store, *filters):
    return IndexScan(store, P_SO, want_sorted_var=0, sip_filters=list(filters))


def _range_rows(store, scan):
    """Rows of the scan's SIP code range: what the seek-or-range rule
    weighs the key count against."""
    f = scan.sip_filters[0]
    lo, hi = f.code_range()
    start = store.seek(scan.range, 0, scan._sort_col_pos, lo)
    return store.seek(scan.range, start, scan._sort_col_pos, hi, "right") - start


def test_scan_sip_key_seek_returns_the_range_and_bloom_rows(monkeypatch):
    """Under a sparse build side the sorted scan seeks each key's run; it
    returns exactly the members the range + bloom path keeps, with none of
    the bloom's false positives, and reads only their rows."""
    store = _seek_store()
    keys = _codes(store, [":s0007", ":s0410", ":s0411", ":s0993"])
    seek = _sip_scan(store, _keys_filter(keys))
    got = _drain(seek)
    monkeypatch.setattr(scan_mod, "SEEK_ROWS_PER_KEY", 1 << 40)  # always range + bloom
    rng = _sip_scan(store, _keys_filter(keys))
    ref = _drain(rng)
    member = np.isin(ref[0], keys)
    np.testing.assert_array_equal(got[0], ref[0][member])
    np.testing.assert_array_equal(got[1], ref[1][member])
    assert len(got[0]) == 12 and set(got[0].tolist()) == set(keys.tolist())
    assert seek.stats.rows_scanned == 12
    assert rng.stats.rows_scanned > 2000
    assert seek.stats.extra["sip_key_seeks"] == 1
    assert "sip_key_seeks" not in rng.stats.extra
    assert rng.stats.extra["sip_probe_dispatches"] > 0


def _seeks(k, n):
    return k * scan_mod.SEEK_ROWS_PER_KEY < n + scan_mod.SEEK_FIXED_ROWS


@pytest.mark.parametrize("sparse", [True, False])
def test_scan_sip_seek_rule_weighs_keys_against_range_rows(sparse):
    """Seek iff keys × SEEK_ROWS_PER_KEY < rows in the code range +
    SEEK_FIXED_ROWS: two keys at the ends of a 2,400-row range seek;
    every subject of :p (1,000 keys, 3,000 rows) keeps range + bloom."""
    store = _seek_store()
    names = ([":s0100", ":s0899"] if sparse
             else [f":s{i:04d}" for i in range(1000)])
    keys = _codes(store, names)
    scan = _sip_scan(store, _keys_filter(keys))
    rows = _drain(scan)
    n = _range_rows(store, scan)
    assert n == (2400 if sparse else 3000)
    assert _seeks(len(keys), n) == sparse
    assert scan.stats.extra.get("sip_key_seeks", 0) == (1 if sparse else 0)
    assert set(rows[0].tolist()) == set(keys.tolist())
    assert len(rows[0]) == 3 * len(keys)
    if sparse:
        assert scan.stats.rows_scanned == 3 * len(keys)
    else:
        assert scan.stats.rows_scanned == n
        assert scan._mask_filters[0].words is not None  # bloom-masked


def test_scan_sip_seek_rule_boundary():
    """Every subject of a span (3 rows a key): just under the rule's
    break-even the scan seeks, just over it it keeps range + bloom."""
    store = _seek_store()
    # k keys, 3k rows: seek iff k * (SEEK_ROWS_PER_KEY - 3) < SEEK_FIXED_ROWS
    edge = -(-scan_mod.SEEK_FIXED_ROWS // (scan_mod.SEEK_ROWS_PER_KEY - 3))  # first k that ranges
    assert edge <= 1000
    for k, seeks in ((edge - 1, 1), (edge, 0)):
        keys = _codes(store, [f":s{i:04d}" for i in range(k)])
        scan = _sip_scan(store, _keys_filter(keys))
        assert len(_drain(scan)[0]) == 3 * k
        n = _range_rows(store, scan)
        assert n == 3 * k
        assert _seeks(k, n) == bool(seeks)
        assert scan.stats.extra.get("sip_key_seeks", 0) == seeks


def test_scan_sip_key_seek_skip_and_reset():
    """skip() on a seeking scan moves its cursor to the first matching row
    whose key is >= the target, never backwards; reset() drops the seek
    state, and the next read seeks afresh."""
    store = _seek_store()
    names = [f":s{i:04d}" for i in range(0, 1000, 50)]  # 20 keys, 60 rows
    keys = _codes(store, names)
    scan = IndexScan(store, P_SO, want_sorted_var=0, sip_filters=[_keys_filter(keys)],
                     sizer=AdaptiveBatchSizer(initial=32, enabled=False))
    b = scan.next_batch()
    first = b.columns[b.col_index(0), : b.n_rows].tolist()
    b.release()
    assert len(first) == 32 and first[0] == keys[0]
    assert scan.stats.extra["sip_key_seeks"] == 1
    scan.skip(0, int(store.dict.lookup(":s0720")))  # between keys 14 and 15
    scan.skip(0, int(keys[3]))  # behind the cursor: no move
    b = scan.next_batch()
    got = b.columns[b.col_index(0), : b.n_rows]
    b.release()
    np.testing.assert_array_equal(got, np.repeat(keys[15:], 3))
    assert scan.next_batch() is None
    assert scan.stats.rows_scanned == 32 + 15
    scan.reset()
    # past the last key before the first read: no row is left in the
    # code range, and the scan ends without seeking
    scan.skip(0, int(keys[-1]) + 1)
    assert scan.next_batch() is None
    scan.reset()
    got = _drain(scan)[0]
    np.testing.assert_array_equal(got, np.repeat(keys, 3))
    assert scan.stats.extra["sip_key_seeks"] == 2


def test_scan_sip_skip_before_the_first_read_holds_in_seek_mode():
    store = _seek_store()
    keys = _codes(store, [":s0010", ":s0500", ":s0900"])
    scan = _sip_scan(store, _keys_filter(keys))
    scan.skip(0, int(store.dict.lookup(":s0400")))
    assert sorted(set(_drain(scan)[0].tolist())) == sorted(keys[1:].tolist())
    assert scan.stats.extra["sip_key_seeks"] == 1


@pytest.mark.parametrize("case", ["duplicates", "outside", "empty", "none_in_range"])
def test_scan_sip_key_seek_edge_key_sets(case):
    store = _seek_store()
    if case == "duplicates":
        keys = _codes(store, [":s0300", ":s0300", ":s0700", ":s0300", ":s0700"])
        want = 6
    elif case == "outside":
        # terms that are no subject of :p widen the code range but own no
        # run: only the one real key's rows come back
        keys = np.concatenate([_codes(store, [":o3", ":s0555", ":o7"]),
                               np.asarray([store.dict.lookup(":p") + 10**6], np.int32)])
        want = 3
    elif case == "none_in_range":
        keys = np.asarray([10**7, 10**7 + 5], np.int32)  # codes past every row
        want = 0
    else:
        keys = np.zeros(0, np.int32)
        want = 0
    f = _keys_filter(keys)
    scan = _sip_scan(store, f)
    got = _drain(scan)
    assert len(got[0]) == want
    assert set(got[0].tolist()) <= set(keys.tolist())
    assert scan.stats.rows_scanned == want
    assert f.words is None  # nothing was bloom-masked
    if case == "duplicates":
        assert len(f.keys) == 2 and list(f.keys) == sorted(f.keys)


def test_scan_sip_two_filters_one_seeks_one_masks():
    """A filter on the sorted var seeks; one on the other var masks the
    rows the seek hands it."""
    store = _seek_store()
    keys = _codes(store, [":s0020", ":s0021", ":s0600", ":s0950"])
    objs = _codes(store, [":o0", ":o1", ":o2"])
    seek_f = _keys_filter(keys, var=0)
    mask_f = _keys_filter(objs, var=1)
    scan = _sip_scan(store, seek_f, mask_f)
    got = _drain(scan)
    assert scan.stats.extra["sip_key_seeks"] == 1
    assert scan.stats.rows_scanned == 12
    assert mask_f.rows_tested == 12 and mask_f.words is not None
    assert seek_f.rows_tested == 0 and seek_f.words is None
    want_s, want_o = [], []
    for k in keys:
        for j in range(3):
            i = int(store.dict.decode(int(k))[2:])
            o = store.dict.lookup(f":o{(i + j) % 11}")
            if o in objs:
                want_s.append(int(k))
                want_o.append(o)
    assert sorted(zip(got[0].tolist(), got[1].tolist())) == sorted(zip(want_s, want_o))


def test_scan_sip_two_sorted_var_filters_seek_their_common_keys():
    store = _seek_store()
    a = _codes(store, [":s0100", ":s0200", ":s0300", ":s0400"])
    b = _codes(store, [":s0200", ":s0400", ":s0401", ":s0402", ":s0900"])
    fa, fb = _keys_filter(a), _keys_filter(b)
    scan = _sip_scan(store, fa, fb)
    got = _drain(scan)
    assert sorted(set(got[0].tolist())) == sorted(_codes(store, [":s0200", ":s0400"]).tolist())
    assert scan.stats.rows_scanned == 6
    assert fa.words is None and fb.words is None


def test_sip_filter_only_sought_never_builds_bloom():
    store = _seek_store()
    f = _keys_filter(_codes(store, [":s0001", ":s0999"]))
    before = ops.dispatch_count("bloom_build")
    _drain(_sip_scan(store, f))
    assert ops.dispatch_count("bloom_build") == before
    assert f.words is None and f.keys is not None
    # the first mask() builds the words
    assert f.mask(_codes(store, [":s0001", ":s0002"]))[0]
    assert ops.dispatch_count("bloom_build") == before + 1 and f.words is not None


def test_scan_sip_counters_charge_the_active_trace():
    from repro.core import telemetry

    store = _seek_store()
    keys = _codes(store, [":s0005", ":s0995"])
    with telemetry.trace_query("t") as tr:
        _drain(_sip_scan(store, _keys_filter(keys)))
        _drain(IndexScan(store, P_SO))  # no SIP: not counted
    assert (tr.sip_scan_rows, tr.sip_seeks) == (6, 1)
    assert tr.summary()["sip_scan_rows"] == 6
    counter = [e for e in tr.chrome_events() if e["ph"] == "C"]
    assert counter[0]["args"] == {"sip_scan_rows": 6, "sip_seeks": 1}


# ---------------------------------------------------------------------------
# planner: annotations, knob, bushy ordering
# ---------------------------------------------------------------------------


def _chain_store():
    store = QuadStore()
    for i in range(12):
        store.add(f":a{i}", ":r1", f":b{i}")
    for i in range(3000):
        store.add(f":b{i % 400}", ":r2", f":c{i % 350}")
        store.add(f":c{i % 350}", ":r3", f":d{i % 400}")
    for i in range(12):
        store.add(f":d{i}", ":r4", f":e{i}")
        store.add(f":e{i}", ":r5", f":f{i}")
    return store.build()


CHAIN_Q = (
    "SELECT ?a ?f { ?a :r1 ?b . ?b :r2 ?c . ?c :r3 ?d . "
    "?d :r4 ?e . ?e :r5 ?f }"
)

_JOINS = (PL.PMergeJoin, PL.PHashJoin, PL.PLookupJoin, PL.PCross)


def _join_children(n):
    return [n.left, n.right] if hasattr(n, "left") else [n.probe, n.build]


def _count_joins(n):
    out = 1 if isinstance(n, _JOINS) else 0
    for fld in ("child", "left", "right", "probe", "build"):
        c = getattr(n, fld, None)
        if isinstance(c, PL.PhysNode):
            out += _count_joins(c)
    return out


def _is_bushy(n):
    if isinstance(n, _JOINS):
        kids = _join_children(n)
        if all(_count_joins(k) >= 1 for k in kids):
            return True
    return any(
        _is_bushy(getattr(n, fld))
        for fld in ("child", "left", "right", "probe", "build")
        if isinstance(getattr(n, fld, None), PL.PhysNode)
    )


def test_bushy_planner_picks_nonlinear_shape():
    store = _chain_store()
    eng = Engine(store, EngineConfig())
    node, vt = eng.parse(CHAIN_Q)
    phys = eng.plan(node)
    assert _is_bushy(phys), PL.explain(phys, vt)
    # and the shape is not just decorative: results match legacy exactly
    got = sorted(map(tuple, eng.execute_plan(phys, vt).rows.tolist()))
    leg = Engine(store, EngineConfig(engine="legacy")).execute(CHAIN_Q)
    assert got == sorted(map(tuple, leg.rows.tolist()))


def test_explain_prints_sip_annotations():
    store = _chain_store()
    eng = Engine(store, EngineConfig(sip="on"))
    node, vt = eng.parse(CHAIN_Q)
    text = PL.explain(eng.plan(node), vt)
    assert "SipFilter(" in text
    assert "sip-export=" in text


def test_sip_off_plans_have_no_annotations():
    store = _chain_store()
    eng = Engine(store, EngineConfig(sip="off"))
    node, vt = eng.parse(CHAIN_Q)
    text = PL.explain(eng.plan(node), vt)
    assert "SipFilter(" not in text


def test_sip_never_pushed_into_optional_side():
    """left_outer: the nullable side must keep unmatched rows, so no SIP
    annotation may land in it."""
    store = _chain_store()
    eng = Engine(store, EngineConfig(sip="on"))
    q = (
        "SELECT ?a ?b ?c { ?a :r1 ?b . "
        "OPTIONAL { ?b :r2 ?c . ?c :r3 ?d . ?d :r4 ?e } }"
    )
    node, vt = eng.parse(q)
    phys = eng.plan(node)

    def exports_in(n):
        out = set(a.sid for a in getattr(n, "sip_exports", ()))
        for fld in ("child", "left", "right", "probe", "build"):
            c = getattr(n, fld, None)
            if isinstance(c, PL.PhysNode):
                out |= exports_in(c)
        return out

    def leaf_sids(n):
        out = set(a.sid for a in getattr(n, "sip", ()))
        for fld in ("child", "left", "right", "probe", "build"):
            c = getattr(n, fld, None)
            if isinstance(c, PL.PhysNode):
                out |= leaf_sids(c)
        return out

    def check(n):
        # a nullable/subtrahend side may only consume filters exported by
        # joins inside that same side — never from across the boundary
        if isinstance(n, _JOINS) and getattr(n, "mode", "inner") in (
            "left_outer", "anti",
        ):
            nullable = _join_children(n)[1]
            outside = leaf_sids(nullable) - exports_in(nullable)
            assert not outside, PL.explain(phys, vt)
        for fld in ("child", "left", "right", "probe", "build"):
            c = getattr(n, fld, None)
            if isinstance(c, PL.PhysNode):
                check(c)

    check(phys)
    # sanity: OPTIONAL results agree with legacy under sip=on
    got = sorted(map(tuple, eng.execute_plan(phys, vt).rows.tolist()))
    leg = Engine(store, EngineConfig(engine="legacy")).execute(q)
    assert got == sorted(map(tuple, leg.rows.tolist()))


# ---------------------------------------------------------------------------
# engine equivalence: SIP is a pure prefilter
# ---------------------------------------------------------------------------

PARITY_QUERIES = [
    CHAIN_Q,
    "SELECT ?a ?c { ?a :r1 ?b . ?b :r2 ?c }",
    "SELECT ?b ?d { ?b :r2 ?c . ?c :r3 ?d . ?d :r4 ?e }",
    "SELECT ?a ?b ?c { ?a :r1 ?b . OPTIONAL { ?b :r2 ?c } }",
    "SELECT ?b { ?b :r2 ?c . MINUS { ?b :r2 :c1 } }",
    "SELECT ?b ?c { ?b :r2 ?c . FILTER NOT EXISTS { ?c :r3 :d3 } }",
    "SELECT ?c (COUNT(?b) AS ?n) { ?b :r2 ?c . ?c :r3 ?d } GROUP BY ?c",
]


@pytest.mark.parametrize("qi", range(len(PARITY_QUERIES)))
def test_engine_parity_sip_on_off(qi):
    store = _chain_store()
    q = PARITY_QUERIES[qi]
    want = None
    for cfg in (
        EngineConfig(engine="legacy"),
        EngineConfig(sip="off"),
        EngineConfig(sip="on"),
        EngineConfig(),  # auto gate
        EngineConfig(sip="on", join_strategy="hash"),
        EngineConfig(sip="on", join_strategy="merge"),
    ):
        res = Engine(store, cfg).execute(q)
        got = sorted(map(tuple, res.rows.tolist()))
        if want is None:
            want = got
        else:
            assert got == want, f"{cfg} diverges on {q}"


def test_sip_actually_prunes_probe_rows():
    """SIP must do real work: either bloom masks prune probe rows or
    range seeks cut storage reads (usually both, depending on whether the
    probe scan is sorted by the filtered var)."""
    store = _chain_store()

    def totals(cfg):
        res = Engine(store, cfg).execute(CHAIN_Q)
        agg = {"scanned": 0, "pruned": 0, "seeks": 0}

        def walk(op):
            agg["scanned"] += op.stats.rows_scanned
            agg["pruned"] += op.stats.extra.get("sip_pruned_rows", 0)
            agg["seeks"] += op.stats.extra.get("sip_range_seeks", 0)
            for c in op.children():
                walk(c)

        walk(res.root)
        return agg

    on = totals(EngineConfig(sip="on"))
    off = totals(EngineConfig(sip="off"))
    assert on["seeks"] > 0
    assert on["pruned"] > 0 or on["scanned"] < off["scanned"]
    assert off["pruned"] == 0 and off["seeks"] == 0


# ---------------------------------------------------------------------------
# serve layer: plan-cache key includes the config fingerprint
# ---------------------------------------------------------------------------


def test_plan_cache_key_includes_config_fingerprint():
    from repro.serve.query_server import QueryServer

    store = _chain_store()
    q = "SELECT ?a ?d { ?a :r1 ?b . ?b :r2 ?c . ?c :r3 ?d }"
    server = QueryServer(store, EngineConfig(sip="off"))
    server.execute("q", q)
    assert len(server._plan_cache) == 1
    # same text, same config: cache hit
    server.execute("q", q)
    assert len(server._plan_cache) == 1
    # reconfigured engine (different fingerprint): must replan, not serve
    # the sip=off-shaped plan
    server.engine = Engine(store, EngineConfig(sip="on"))
    server.execute("q", q)
    assert len(server._plan_cache) == 2
    (k1, (p1, _, _)), (k2, (p2, _, _)) = sorted(server._plan_cache.items())
    texts = {PL.explain(p1), PL.explain(p2)}
    assert any("SipFilter(" in t for t in texts)
    assert any("SipFilter(" not in t for t in texts)


def test_engine_plan_fingerprint_covers_knobs():
    store = _scan_store()
    fps = {
        Engine(store, cfg).plan_fingerprint()
        for cfg in (
            EngineConfig(),
            EngineConfig(sip="on"),
            EngineConfig(sip="off"),
            EngineConfig(join_strategy="hash"),
            EngineConfig(engine="legacy"),
        )
    }
    assert len(fps) == 5
