"""Benchmark harness — one suite per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--json out.json]

Prints ``name,us_per_call,derived`` CSV blocks per suite:
  Fig 6a  LSQB CPU-bound joins           (bench_lsqb)
  Fig 6b  BSBM Explore OLTP              (bench_bsbm_explore)
  Fig 6c  BSBM Business Intelligence     (bench_bsbm_bi)
  List. 3 adaptive vs fixed batch size   (bench_adaptive)
  List. 1/5 operator microbenchmarks     (bench_operators)

With ``--json <path>`` the same per-suite ``us_per_call`` rows are written
as a JSON document (suite → [{name, us_per_call, derived}]) so perf
trajectories can be tracked across PRs (see BENCH_PR1.json).

With ``--trace-out <path>`` an end-to-end telemetry smoke runs after the
suites: one LSQB query executes under EXPLAIN ANALYZE (report printed),
its QueryTrace is written as Chrome-trace JSON (loadable in Perfetto),
and a small served workload's metrics registry is written next to it as
``<path>.metrics.json`` — CI uploads both as artifacts. The smoke also
exercises the PR 8 workload-history surface (DESIGN.md §14): the served
workload runs under ``cardinality_feedback="apply"`` with a flight
recorder attached, a misestimating query's first run must trigger a
q-error flight capture (bundle under ``artifacts/flight/``), the
OpenMetrics exposition is written as ``<path>.metrics.prom`` and passes
``validate_openmetrics``, and the workload repository JSONL round-trips
through save/load as ``<path>.workload.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List

from repro import compile_cache


def _parse_rows(csv_block: str) -> List[Dict[str, object]]:
    """CSV block emitted by benchmarks.common.Suite → row dicts."""
    rows: List[Dict[str, object]] = []
    for line in csv_block.splitlines():
        if line.startswith("#") or line.startswith("name,") or not line.strip():
            continue
        name, us, derived = line.split(",", 2)
        rows.append({"name": name, "us_per_call": float(us), "derived": derived})
    return rows


def telemetry_smoke(trace_out: str, fast: bool = True) -> None:
    """EXPLAIN ANALYZE + trace/metrics export smoke (DESIGN.md §13):
    exercises the full telemetry surface end-to-end and leaves artifacts
    CI can upload. Validates the trace is well-formed Chrome-trace JSON."""
    from repro.core import Engine, EngineConfig
    from repro.data import LSQB_QUERIES, generate_social_graph
    from repro.serve.query_server import QueryServer

    store, meta = generate_social_graph(scale=0.02 if fast else 0.05)
    engine = Engine(store, EngineConfig(engine="barq"))
    res = engine.execute(LSQB_QUERIES["q6"])
    print(f"# EXPLAIN ANALYZE lsqb q6 ({meta['n_triples']} triples, "
          f"{res.n_rows} rows):")
    print(res.explain_analyze())
    res.trace.save_chrome_trace(trace_out)
    with open(trace_out) as fh:
        doc = json.load(fh)
    assert doc["traceEvents"], "trace export produced no events"
    assert all("ph" in ev and "pid" in ev for ev in doc["traceEvents"])
    print(f"# wrote {trace_out} ({len(doc['traceEvents'])} events)")

    from repro.serve.flight_recorder import FlightRecorder
    from repro.serve.metrics import validate_openmetrics
    from repro.serve.workload_repo import WorkloadRepository

    # served workload under cardinality feedback with a flight recorder:
    # q6's first run misestimates badly enough (planner has no history)
    # that the q-error trigger must capture a bundle (DESIGN.md §14)
    flight = FlightRecorder(out_dir="artifacts/flight", q_error_threshold=16.0)
    server = QueryServer(
        store,
        EngineConfig(engine="barq", cardinality_feedback="apply"),
        flight=flight,
    )
    reqs = [("q1", LSQB_QUERIES["q1"]), ("q6", LSQB_QUERIES["q6"])] * 3
    server.run_workload(reqs, warmup=2)
    metrics_out = trace_out + ".metrics.json"
    server.metrics.save(metrics_out)
    print(f"# wrote {metrics_out}")

    assert flight.n_captures >= 1, "flight recorder captured no outlier"
    bundle_dir = sorted(
        os.path.join("artifacts/flight", p)
        for p in os.listdir("artifacts/flight")
    )[-1]
    for fname in ("trace.json", "explain.txt", "meta.json"):
        assert os.path.exists(os.path.join(bundle_dir, fname)), (
            f"missing {fname} in bundle"
        )
    with open(os.path.join(bundle_dir, "meta.json")) as fh:
        meta_doc = json.load(fh)
    assert meta_doc["reasons"], "capture bundle records no trigger reason"
    print(f"# flight capture: {bundle_dir} (reasons: {meta_doc['reasons']})")

    # the repeated q6 must have re-planned with observed cardinalities:
    # a fresh run's worst plan-node q-error collapses vs the cold first run
    r_warm = server.execute("q6-warm", LSQB_QUERIES["q6"])
    assert r_warm.max_q_error <= 4.0, (
        f"feedback did not converge: warm q6 max_q_error={r_warm.max_q_error}"
    )
    print(f"# feedback loop: warm q6 max_q_error={r_warm.max_q_error:.2f} "
          f"(cold run triggered the capture above)")

    prom_out = trace_out + ".metrics.prom"
    exposition = server.openmetrics()
    families = validate_openmetrics(exposition)
    with open(prom_out, "w") as fh:
        fh.write(exposition)
    print(f"# wrote {prom_out} ({len(families)} metric families, "
          f"format-validated)")

    workload_out = trace_out + ".workload.jsonl"
    n_saved = server.workload.save(workload_out)
    reloaded = WorkloadRepository()
    n_loaded = reloaded.load(workload_out)
    assert n_loaded == n_saved, "workload JSONL did not round-trip"
    assert len(reloaded.feedback.snapshot()) == len(
        server.workload.feedback.snapshot()
    ), "feedback store did not round-trip"
    print(f"# wrote {workload_out} ({n_saved} fingerprints, "
          f"{len(reloaded.feedback.snapshot())} feedback entries, "
          f"reload-verified)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller scales")
    ap.add_argument("--suite", default="all",
                    choices=("all", "lsqb", "explore", "bi", "adaptive", "ops"))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-suite us_per_call results as JSON")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="run the telemetry smoke and write Chrome-trace "
                         "JSON (+ .metrics.json) artifacts")
    args = ap.parse_args()
    f = args.fast
    compile_cache.enable()

    from benchmarks import (
        bench_adaptive,
        bench_bsbm_bi,
        bench_bsbm_explore,
        bench_lsqb,
        bench_operators,
    )

    suites = {
        "lsqb": lambda: bench_lsqb.run(scale=0.03 if f else 0.05,
                                       runs=2 if f else 3),
        "explore": lambda: bench_bsbm_explore.run(scale=0.1 if f else 0.2,
                                                  runs=3 if f else 5),
        "bi": lambda: bench_bsbm_bi.run(scale=0.08 if f else 0.15,
                                        runs=2 if f else 3),
        "adaptive": lambda: bench_adaptive.run(scale=0.1 if f else 0.2,
                                               runs=3 if f else 5),
        "ops": lambda: bench_operators.run(fast=f),
    }
    selected = suites if args.suite == "all" else {args.suite: suites[args.suite]}
    report: Dict[str, object] = {}
    for name, fn in selected.items():
        t0 = time.time()
        out = fn()
        print(out)
        print(f"# suite {name} finished in {time.time() - t0:.1f}s\n", flush=True)
        report[name] = _parse_rows(out)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2)
        print(f"# wrote {args.json}")
    if args.trace_out:
        telemetry_smoke(args.trace_out, fast=f)


if __name__ == "__main__":
    main()
