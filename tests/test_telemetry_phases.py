"""Kernel dispatch traced from inside the program (DESIGN.md §13): each
dispatch of a traced request is a span with a parent, its self time cut
into stage / launch / wait / copy / finish, its host→device, device→host
and padding bytes counted, and the compiles it caused charged to it; the
served path has parse and plan spans. Pallas kernels run in the TPU
interpreter on a tiny store."""

import jax
import numpy as np
import pytest

from repro.core import EngineConfig, QuadStore, telemetry
from repro.kernels import hash_join, ops, sorted_search, tiling
from repro.serve.query_server import QueryServer

# two hash joins on a 60-person chain; the planner's choice, forced to hash
JOIN = "SELECT ?a ?b ?x { ?a :knows ?b . ?b :age ?x . ?b :knows ?c }"


def _chain_store(n=60):
    store = QuadStore()
    for i in range(n):
        store.add(f":p{i}", ":knows", f":p{(i * 7 + 1) % n}")
        store.add(f":p{i}", ":age", 20 + i % 30)
    return store.build()


@pytest.fixture(scope="module")
def served():
    """A served request's trace on the Pallas plane."""
    srv = QueryServer(_chain_store(), EngineConfig(join_strategy="hash"))
    with ops.data_plane("pallas"):
        r = srv.execute("join", JOIN)
    assert r.n_rows > 0 and r.trace.dispatches
    return r.trace


def _self_time(d, dispatches):
    return (d.t1 - d.t0) - sum(c.t1 - c.t0 for c in dispatches if c.parent == d.id)


def test_served_dispatch_phases_sum_to_self_time(served):
    assert {d.trace_id for d in served.dispatches} == {served.id}
    assert any(d.trips for d in served.dispatches)
    for d in served.dispatches:
        ph = d.phases()
        assert set(ph) == set(telemetry.PHASES)
        assert all(v >= -1e-9 for v in ph.values()), (d.kernel, ph)
        own = _self_time(d, served.dispatches)
        assert sum(ph.values()) == pytest.approx(own, rel=0.05, abs=1e-6)
        for a, b, c, e in d.trips:
            assert d.t0 <= a <= b <= c <= e <= d.t1


def test_nested_dispatch_is_charged_to_the_child_once():
    keys = np.arange(3000, dtype=np.int32)[::-1].copy()
    with ops.data_plane("pallas"), telemetry.trace_query("build") as tr:
        ops.hash_build(None, keys, 8)
    radix, build = tr.dispatches  # children end first
    assert (radix.kernel, build.kernel) == ("radix_partition", "hash_build")
    assert radix.parent == build.id and build.parent is None
    assert build.kids == [(radix.t0, radix.t1)]
    # hash_build's own round trip is its sort; radix_partition's is its kernel
    assert len(build.trips) == len(radix.trips) == 1
    total = sum(sum(d.phases().values()) for d in tr.dispatches)
    assert total == pytest.approx(build.t1 - build.t0, rel=1e-6)
    # the ledger keeps its inclusive wall times
    assert tr.ledger.wall_s["hash_build"] == pytest.approx(build.t1 - build.t0)


@pytest.mark.parametrize("n_build,n_probe", [(3000, 100), (2048, 1025)])
def test_hash_probe_bytes_equal_its_padded_shapes(n_build, n_probe):
    rng = np.random.default_rng(n_build)
    skeys = np.sort(rng.integers(0, 1 << 20, n_build)).astype(np.int32)
    spid = np.zeros(n_build, np.int32)
    part_starts = np.array([0, n_build], np.int32)
    qkeys = rng.integers(0, 1 << 20, n_probe).astype(np.int32)
    with ops.data_plane("pallas"), telemetry.trace_query("probe") as tr:
        ops.hash_probe(spid, None, skeys, None, qkeys, part_starts, 1)
    (d,) = tr.dispatches
    build = tiling.bucket(n_build, hash_join.N_TILE)
    probe = tiling.bucket(n_probe, hash_join.BLOCK)
    # six int32 inputs padded (pid, hi, lo of each side); two int32 outputs
    assert d.pad_logical_bytes == 3 * 4 * (n_build + n_probe)
    assert d.pad_bytes == 3 * 4 * (build + probe)
    assert d.h2d_bytes == d.pad_bytes
    assert d.d2h_bytes == 2 * 4 * probe
    assert len(d.trips) == 1


def test_a_fresh_bucket_charges_one_compile_to_its_kernel():
    keys = np.arange(5 * sorted_search.K_TILE, dtype=np.int32)
    queries = np.arange(3 * sorted_search.Q_BLOCK, dtype=np.int32)
    jax.clear_caches()
    before = telemetry.compile_ledger().snapshot()
    with ops.data_plane("pallas"), telemetry.trace_query("compile") as tr:
        ops.sorted_search(keys, queries)
        ops.sorted_search(keys, queries)
    first, second = tr.dispatches
    own = [c for c in first.compiles if "sorted_search_kernel" in c[0]]
    assert len(own) == 1
    program, seconds, shapes = own[0]
    assert seconds > 0
    assert shapes == ((tiling.bucket(len(keys), sorted_search.K_TILE),),
                      (tiling.bucket(len(queries), sorted_search.Q_BLOCK),))
    assert second.compiles == []
    after = telemetry.compile_ledger()
    events = after.events[before[0]:]
    assert after.snapshot()[0] - before[0] == len(events) >= 1
    assert ("sorted_search", program, seconds, shapes) in events


def test_plan_cache_miss_has_parse_and_plan_spans_and_a_hit_neither():
    srv = QueryServer(_chain_store())
    q = "SELECT ?a { ?a :age ?x . FILTER(?x > 30) }"
    miss, hit = srv.execute("m", q), srv.execute("h", q)
    names = [[s[0] for s in r.trace.spans] for r in (miss, hit)]
    assert names[0] == ["plan_cache", "parse", "plan", "translate", "execute"]
    assert names[1] == ["plan_cache", "translate", "execute"]
    assert [r.trace.spans[0][4] for r in (miss, hit)] == [{"hit": False}, {"hit": True}]
    # the server keeps its finished traces among the recent ones
    assert telemetry.recent_traces()[-2:] == [miss.trace, hit.trace]


def test_telemetry_off_serves_without_a_trace():
    srv = QueryServer(_chain_store(), EngineConfig(telemetry=False))
    r = srv.execute("q", "SELECT ?a { ?a :age ?x }")
    assert r.trace is None and r.n_rows == 60


def test_perfetto_export_nests_phase_events_in_their_dispatch(served):
    ev = served.chrome_events()
    kernels = {e["args"]["id"]: e for e in ev if e.get("cat") == "kernel"}
    phases = [e for e in ev if e.get("cat") == "phase"]
    assert {e["name"] for e in phases} >= {"stage", "launch", "wait", "copy"}
    assert len(kernels) == len(served.dispatches)
    by_kernel = {}
    for e in kernels.values():
        by_kernel.setdefault(e["name"], []).append(e)
    for p in phases:
        assert any(k["ts"] - 1e-3 <= p["ts"] and p["ts"] + p["dur"] <= k["ts"] + k["dur"] + 1e-3
                   for k in by_kernel[p["args"]["kernel"]])
    launch = next(e for e in kernels.values() if e["args"]["h2d_bytes"] > 0)
    assert set(launch["args"]["self_ms"]) == set(telemetry.PHASES)
