"""The benchmark's metric arithmetic on small synthetic inputs: percentiles
over all requests, unions of nested intervals, the layers a request's time is cut into, the idle
share, the roofline share, and the reduction of a device trace."""

import pytest

from bench.harness import devtrace, stats
from bench.harness.devtrace import Call, DeviceTrace
from bench.harness.record import Done, Run
from bench.harness.runner import Cell, breakdown, host_activity
from bench.tests.tiny_bench import REPO


def reader(metric):
    return Cell(REPO, "bsbm-25m.explore").reader(metric)


@pytest.mark.parametrize("values,p,want", [
    ([5.0], 50, 5.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.0),
    ([1.0, 2.0, 3.0, 4.0], 90, 4.0),
    (list(range(1, 101)), 90, 90),
    (list(range(100, 0, -1)), 50, 50),
])
def test_percentile_is_nearest_rank_over_all_values(values, p, want):
    assert stats.percentile(values, p) == want


def test_percentile_of_nothing_fails():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def requests(*spec):
    return [Done(f"{q}.{i}", q, t0, t1, [], 3) for i, (q, t0, t1) in enumerate(spec)]


def test_end_to_end_readers():
    reqs = requests(("a", 0.0, 0.1), ("b", 0.1, 0.5), ("a", 0.5, 0.8), ("b", 0.8, 1.0))
    run = Run(reqs, (0.0, 2.0), 12.5, mix=["a", "b", "b"])
    # a's mean 0.2 s, b's 0.3 s: a mix of a, b, b takes 0.8 s
    assert reader("qmph")(run) == pytest.approx(3600 / 0.8)
    assert reader("qps")(run) == 2.0
    assert reader("p50_ms")(run) == pytest.approx(200.0)
    assert reader("p90_ms")(run) == pytest.approx(400.0)
    assert reader("setup_s")(run) == 12.5
    assert reader("dispatches")(run) == 3.0
    # no trace: the per-layer readers find nothing
    for m in ("host_ms", "operator_ms", "dispatch_host_ms", "device_ms", "idle_pct",
              "roofline_pct"):
        assert reader(m)(run) is None


def test_mixes_per_hour_do_not_hang_on_where_the_window_cuts_the_mix():
    """Two windows of one mix of a slow and a fast query, one cut after the
    slow request and one after the fast request that follows it: requests
    per second differ, mixes per hour do not."""
    slow_fast = [("slow", 0.0, 0.9), ("fast", 0.9, 1.0)] * 3
    short = Run(requests(*slow_fast[:5]), (0.0, 2.95), 0.0, mix=["slow", "fast"])
    longer = Run(requests(*slow_fast[:6]), (0.0, 3.05), 0.0, mix=["slow", "fast"])
    assert reader("qps")(longer) / reader("qps")(short) > 1.15
    assert reader("qmph")(short) == pytest.approx(reader("qmph")(longer)) == pytest.approx(3600)


def test_mixes_per_hour_need_every_query_of_the_mix():
    run = Run(requests(("a", 0.0, 0.1)), (0.0, 1.0), 0.0, mix=["a", "b"])
    assert reader("qmph")(run) is None
    assert reader("qmph")(Run(requests(("a", 0.0, 0.1)), (0.0, 1.0), 0.0)) is None


def test_union_counts_nested_and_overlapping_intervals_once():
    assert stats.union([(0, 10), (2, 3), (9, 12), (20, 21), (5, 5)]) == [(0, 12), (20, 21)]
    assert stats.length([(0, 10), (2, 3), (9, 12)]) == 12


def test_intersect_clip_and_gaps():
    xs = [(0, 2), (4, 6)]
    assert stats.intersect(xs, [(1, 5)]) == [(1, 2), (4, 5)]
    assert stats.clip([(0, 3), (2, 6)], 1, 4) == [(1, 4)]
    assert stats.gaps(xs, -1, 7) == [(-1, 0), (2, 4), (6, 7)]
    assert stats.gaps([], 0, 1) == [(0, 1)]


def traced_run():
    """Two requests; the first holds a kernel call with a nested call,
    device ops inside both, and one op outside every call."""
    r1 = Done("a.0", "a", 0.0, 1.0, [(0.1, 0.9)], 2)
    r2 = Done("b.0", "b", 1.0, 2.0, [(1.2, 1.8)], 1)
    calls = [Call("hash_build", 0.2, 0.6, (1e9, 1e6)),
             Call("radix_partition", 0.25, 0.35, (4e9, 8e6)),
             Call("gather_emit", 1.3, 1.5, None)]
    ops = [(0.3, 0.32, "radix_kernel"), (0.4, 0.5, "sort"), (1.35, 1.45, "gather"),
           (1.9, 1.95, "stray")]
    trace = DeviceTrace(ops, [(0.3, 0.32), (0.4, 0.5), (1.35, 1.45)], 1)
    devtrace.attribute(calls, trace)
    return Run([r1, r2], (0.0, 2.0), 1.0, calls, trace,
               {"ops_per_s": 1e12, "hbm_bytes_per_s": 1e9})


def test_attribute_gives_each_op_to_its_innermost_call():
    run = traced_run()
    build, radix, emit = run.calls
    assert radix.ops == {"radix_kernel": pytest.approx(0.02)}
    assert build.ops == {"sort": pytest.approx(0.1)}
    assert emit.device_s == pytest.approx(0.1)


def test_layers_add_up_to_each_request_latency():
    run = traced_run()
    for r, layers in zip(run.requests, run.layers):
        assert sum(layers.values()) == pytest.approx(r.latency_s)
    first = run.layers[0]
    assert first["host"] == pytest.approx(0.2)  # outside the spans
    assert first["device"] == pytest.approx(0.12)
    assert first["dispatch_host"] == pytest.approx(0.4 - 0.12)
    assert first["operator"] == pytest.approx(0.8 - 0.4)
    assert reader("device_ms")(run) == pytest.approx(1e3 * (0.12 + 0.1) / 2)  # the stray op is in no call


def test_idle_share_of_the_window():
    run = traced_run()
    assert reader("idle_pct")(run) == pytest.approx(100 * (1 - 0.27 / 2.0))


def test_roofline_share_sums_least_times_over_device_times():
    run = traced_run()
    # hash_build: max(1e9 / 1e12, 1e6 / 1e9) = 1e-3 s over its own 0.1 s;
    # radix_partition: max(4e-3, 8e-3) over 0.02 s; gather_emit has no cost
    want = 100 * (1e-3 + 8e-3) / (0.1 + 0.02)
    assert reader("roofline_pct")(run) == pytest.approx(want)


def test_roofline_share_is_absent_without_costs():
    run = traced_run()
    for c in run.calls:
        c.cost = None
    assert reader("roofline_pct")(run) is None


def test_op_name_from_instruction_text():
    text = "%hash_probe_kernel.1 = (s32[4096]) custom-call(s32[2048] %bpid.1), x=y"
    assert devtrace.op_name(text) == "hash_probe_kernel.1"
    assert devtrace.op_name("fusion.3") == "fusion.3"


@pytest.mark.parametrize("late_s", [-0.0009, 0.0, 0.0004, 0.002])
def test_align_recovers_the_device_clock_shift(late_s):
    calls, modules, t = [], [], 0.0
    for i in range(300):
        d = 0.0005 + 0.002 * ((i * 7919) % 13) / 13
        calls.append(Call("k", t, t + d, None))
        run_s = 0.00005 + 0.0001 * (i % 3)
        modules.append((t + d - run_s - 1e-5 + late_s, t + d - 1e-5 + late_s))
        if i % 5 == 0:  # a nested call that launches nothing
            calls.append(Call("inner", t + 1e-5, t + 2e-5, None))
        t += d + 0.0002
    shift, inside = devtrace.align(calls, modules)
    assert inside == len(modules)
    assert abs(shift + late_s) < 2e-4


def test_breakdown_names_device_ops_and_idle_gaps():
    run = traced_run()
    out = breakdown(run)
    names = dict((n, s) for n, s in out["device_ops"])
    assert names["hash_build/sort"] == pytest.approx(0.1)
    assert len(out["idle_gaps"]) <= 10
    assert host_activity(run, 0.0, 0.1) == "planner@a"
    assert host_activity(run, 0.22, 0.24) == "dispatch.hash_build@a"
    assert host_activity(run, 0.65, 0.85) == "operators@a"
