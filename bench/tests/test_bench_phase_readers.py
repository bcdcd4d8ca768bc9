"""The readers of the program's own spans (``bench/harness/phases.py``)
on a synthetic traced run: two requests, one with a nested kernel
dispatch that made device round trips, one planned from the cache with a
dispatch that made none; the phase split, the byte and compile counters,
the naming of idle gaps, and a program that keeps no spans."""

import pytest

from bench.harness import phases
from bench.harness.devtrace import DeviceTrace
from bench.harness.record import Done, Run
from bench.harness.runner import Cell
from bench.tests.tiny_bench import REPO
from repro.core import telemetry

READERS = ("parse_ms", "plan_ms", "stage_ms", "launch_ms", "wait_ms", "copy_ms", "finish_ms",
           "h2d_mb", "pad_pct", "compile_ms")


def reader(metric):
    return Cell(REPO, "bsbm-25m.explore").reader(metric)


def dispatch(tr, kernel, t0, t1, trips=(), parent=None, h2d=0, logical=0, padded=0,
             compiles=()):
    d = telemetry.Dispatch(kernel, "pallas", tr.id, None if parent is None else parent.id)
    d.t0, d.t1 = t0, t1
    for trip in trips:
        d.add_trip(*trip, h2d_bytes=0, d2h_bytes=0)
    d.h2d_bytes, d.pad_logical_bytes, d.pad_bytes = h2d, logical, padded
    d.compiles = list(compiles)
    if parent is not None:
        parent.kids.append((t0, t1))
    tr.dispatches.append(d)
    return d


def traces():
    """Request a (a plan-cache miss) holds hash_build [0.2, 0.6], whose
    child radix_partition is [0.25, 0.35]; request b (a hit) holds a
    gather_emit with no round trip and a compile outside any dispatch. A
    warm-up trace lies outside every request."""
    a = telemetry.QueryTrace("a")
    a.t0 = 0.01
    for name, t0, dur in (("plan_cache", 0.01, 0.001), ("parse", 0.011, 0.009),
                          ("plan", 0.02, 0.03), ("translate", 0.05, 0.05),
                          ("execute", 0.1, 0.8)):
        a.add_span(name, "query", t0, dur)
    build = dispatch(a, "hash_build", 0.2, 0.6, [(0.41, 0.45, 0.5, 0.52)], h2d=3e6,
                     logical=2e6, padded=3e6,
                     compiles=[("jit(hash_build_order)", 0.04, ((1024,),))])
    dispatch(a, "radix_partition", 0.25, 0.35, [(0.27, 0.3, 0.31, 0.33)], parent=build,
             h2d=1e6, logical=5e5, padded=1e6)
    a.dispatches.reverse()  # children end first
    b = telemetry.QueryTrace("b")
    b.t0 = 1.05
    for name, t0, dur in (("plan_cache", 1.05, 0.001), ("translate", 1.1, 0.1),
                          ("execute", 1.2, 0.6)):
        b.add_span(name, "query", t0, dur)
    dispatch(b, "gather_emit", 1.3, 1.5)
    b.compiles.append(("jit(convert_element_type)", 0.05, ()))
    warm = telemetry.QueryTrace("warm")
    warm.t0 = -5.0
    return [warm, a, b]


def run(late=False):
    """The window [0, 2] holds both requests; with ``late`` it closes at
    1.5, and b ends after it."""
    reqs = [Done("a.0", "a", 0.0, 1.0, [(0.05, 0.9)], 2),
            Done("b.0", "b", 1.0, 2.0, [(1.1, 1.8)], 1)]
    ops = [(0.3, 0.32, "radix_kernel"), (0.45, 0.5, "sort"), (1.9, 1.95, "stray")]
    if late:
        return Run(reqs[:1], (0.0, 1.5), 1.0, [], DeviceTrace(ops, [], 1), late=reqs[1:])
    return Run(reqs, (0.0, 2.0), 1.0, [], DeviceTrace(ops, [], 1))


@pytest.fixture
def recent(monkeypatch):
    kept = traces()
    monkeypatch.setattr(telemetry, "recent_traces", lambda: kept)
    return kept


def test_phase_seconds_charge_nested_calls_to_the_innermost(recent):
    _, a, _ = recent
    got = phases.phase_seconds(a.dispatches)
    # radix_partition: stage 0.02, launch 0.03, wait 0.01, copy 0.02, finish 0.02;
    # hash_build less its child: stage 0.11, launch 0.04, wait 0.05, copy 0.02, finish 0.08
    want = {"stage": 0.13, "launch": 0.07, "wait": 0.06, "copy": 0.04, "finish": 0.10}
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(0.4)  # hash_build's span, counted once
    # the program's own cut agrees
    assert a.phase_totals() == pytest.approx(want)


def test_window_traces_match_each_request_to_its_trace(recent):
    warm, a, b = recent
    pairs = phases.window_traces(run())
    assert [(r.key, tr) for r, tr in pairs] == [("a.0", a), ("b.0", b)]


@pytest.mark.parametrize("metric,want", [
    ("parse_ms", 1e3 * 0.009 / 2),
    ("plan_ms", 1e3 * 0.03 / 2),
    ("stage_ms", 1e3 * (0.13 + 0.2) / 2),  # gather_emit made no round trip: all stage
    ("launch_ms", 1e3 * 0.07 / 2),
    ("wait_ms", 1e3 * 0.06 / 2),
    ("copy_ms", 1e3 * 0.04 / 2),
    ("finish_ms", 1e3 * 0.10 / 2),
    ("h2d_mb", 4.0 / 2),
    ("pad_pct", 100 * (4e6 - 2.5e6) / 4e6),
    ("compile_ms", 1e3 * (0.04 + 0.05) / 2),
])
def test_phase_readers(recent, metric, want):
    assert reader(metric)(run()) == pytest.approx(want)


def test_phase_readers_find_nothing_in_a_program_without_spans(monkeypatch):
    monkeypatch.delattr(telemetry, "recent_traces")
    for m in READERS:
        assert reader(m)(run()) is None
    assert phases.idle_phases(run()) is None


def test_phase_readers_find_nothing_outside_the_window(monkeypatch):
    monkeypatch.setattr(telemetry, "recent_traces", lambda: traces()[:1])
    for m in READERS:
        assert reader(m)(run()) is None


def test_idle_phases_name_each_gap_by_the_program_span_covering_most(recent):
    # gaps: [0.5, 1.9] mostly b's operators, [0, 0.3] a's operators,
    # [0.32, 0.45] hash_build's staging, [1.95, 2] nothing of the program
    assert phases.idle_phases(run()) == [
        ["operators@b", pytest.approx(1.4)],
        ["operators@a", pytest.approx(0.3)],
        ["stage.hash_build@a", pytest.approx(0.13)],
        ["client", pytest.approx(0.05)],
    ]


def test_idle_phases_count_the_request_that_ends_after_the_window(recent):
    # [0.5, 1.5]: a's operators 0.3 s; b's spans hold 0.4 s of the rest, so
    # the program's spans leave the client 0.2 s, not 0.6 s
    assert phases.idle_phases(run(late=True))[0] == ["operators@a", pytest.approx(1.0)]
    # the readers read the window's requests alone
    assert [r.key for r, _ in phases.window_traces(run(late=True))] == ["a.0"]


def test_labelled_intervals_cover_each_dispatch_once(recent):
    _, a, _ = recent
    spans = phases.labelled_intervals(a, "a")
    dispatch_s = sum(sum(b - x for x, b in iv) for name, iv in spans.items()
                     if "." in name)
    assert dispatch_s == pytest.approx(0.4)
    assert spans["parse@a"] == [(0.011, pytest.approx(0.02))]
