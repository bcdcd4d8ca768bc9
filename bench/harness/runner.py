"""One run of one cell: build the store from the seed, warm up the cell's
own shapes, serve its traffic through ``QueryServer.execute`` with one
closed-loop client for ``--seconds``, check every completed answer
against the plain reference, and print the result.

Everything that belongs to one configuration, mix or metric is found by
name: ``BENCHMARK.json`` names the cell; ``configs/<config>.json`` its
sizes and ``generators/<generator>.py`` its store; ``traffic/<mix>.json``
its requests, ``queries/<set>.json`` their texts and
``references/<set>.py`` their plain answers; ``metrics/<family>.py`` the
reader of each metric (``<family>.<suffix>`` names share one reader);
``rooflines/<kernel>.py`` each kernel's logical work; ``peaks.json`` the
chip's peaks by ``device_kind``.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
import traceback
from typing import Dict, List, Optional, Sequence

from bench.harness import check, devtrace, stats
from bench.harness.compiles import CompileCounter
from bench.harness.record import Done, Run
from bench.harness.traffic import Traffic

BENCH = "bench"


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_ext_" + "_".join(path.with_suffix("").parts[-2:]).replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, handed to the program through the variable it honours."""
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    from repro import compile_cache

    jax.config.update("jax_compilation_cache_dir", path)
    return compile_cache.enable()


def find_devices(chips: int) -> dict:
    """The accelerator this run measures; refuses anything but TPU chips,
    and fewer of them than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"JAX finds {len(devs)} {devs[0].platform} device(s); "
                         f"this cell needs {chips} TPU chip(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "_device": devs[0]}


def peak_memory(device) -> Optional[int]:
    mem = device.memory_stats() if device is not None else None
    return None if not mem else int(mem.get("peak_bytes_in_use", 0))


class Cell:
    """A cell's manifest entries and files, found by name under ``root``."""

    def __init__(self, root: pathlib.Path, name: str):
        self.root = root
        bench = root / BENCH
        self.manifest = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.workload = cells[name]
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config = json.loads((root / configs[self.workload["config"]]["file"]).read_text())
        self.mix = json.loads((bench / "traffic" / f"{self.workload['traffic']}.json").read_text())
        self.queries = json.loads((bench / "queries" / f"{self.mix['queries']}.json").read_text())
        self.generator = load_module(bench / "generators" / f"{self.config['generator']}.py")
        self.reference_path = bench / "references" / f"{self.mix['queries']}.py"
        self.bench = bench

    def metrics(self, traced: bool) -> List[dict]:
        """The metrics this cell reports: its end-to-end metrics, or with
        ``traced`` its per-layer metrics."""
        name = self.workload["name"]
        e2e = [m for m in self.manifest["end_to_end"]
               if name in m.get("workloads", [name])]
        if not traced:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.manifest["per_layer"]
                if name in m["workloads"] or ("workloads" not in m and m["moves"] in moved)]

    def reader(self, metric: str):
        return load_module(self.bench / "metrics" / f"{metric.split('.')[0]}.py").read

    def costs(self, kernels: Sequence[str]) -> Dict[str, object]:
        out = {}
        for k in kernels:
            path = self.bench / "rooflines" / f"{k}.py"
            if path.exists():
                out[k] = load_module(path).cost
        return out

    def peaks(self, kind: str) -> dict:
        table = json.loads((self.bench / "peaks.json").read_text())["devices"]
        if kind not in table:
            raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
        return table[kind]


def serve(server, requests, seconds: float, traced: bool, log=print):
    """Closed loop, one client: send the next request when the previous
    one has answered, until ``seconds`` have passed. Returns every request
    sent as (request, t0, t1, result or None, traceback or None), and the
    window's bounds. The last one may end after the window."""
    import jax

    sent = []
    start = time.perf_counter()
    end = start + seconds
    for req in requests:
        t0 = time.perf_counter()
        if t0 >= end:
            break
        result = error = None
        try:
            if traced:
                with jax.profiler.TraceAnnotation(devtrace.REQUEST):
                    result = server.execute(req.key, req.text)
            else:
                result = server.execute(req.key, req.text)
        except Exception:  # noqa: BLE001 - a request that raises is counted as failed
            error = traceback.format_exc()
        sent.append((req, t0, time.perf_counter(), result, error))
    log(f"window requests_sent={len(sent)} "
        f"completed={sum(t1 <= end and r is not None for _, _, t1, r, _ in sent)} "
        f"errors={sum(t1 <= end and e is not None for _, _, t1, _, e in sent)} "
        f"last_end_s={time.perf_counter() - start}")
    return sent, (start, end)


def per_query_lines(records: Sequence[Done]) -> List[str]:
    by_query: Dict[str, List[Done]] = {}
    for r in records:
        by_query.setdefault(r.query, []).append(r)
    out = []
    for q, rs in by_query.items():
        lat = [1e3 * r.latency_s for r in rs]
        out.append(f"query {q} n={len(rs)} mean_ms={sum(lat) / len(lat)} "
                   f"min_ms={min(lat)} max_ms={max(lat)} "
                   f"rows={sum(r.rows for r in rs)} "
                   f"dispatches={sum(r.dispatches for r in rs)}")
    return out


def main(argv: Optional[Sequence[str]] = None, root: Optional[pathlib.Path] = None,
         t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = pathlib.Path(root or pathlib.Path(__file__).resolve().parents[2])
    cell = Cell(root, args.workload)
    traced = bool(args.trace)

    cache_dir = enable_compile_cache(root)
    dev = find_devices(int(cell.workload["chips"]))
    device = dict((k, v) for k, v in dev.items() if not k.startswith("_"))
    print(f"device platform={device['platform']} device_kind={device['kind']} "
          f"count={device['count']}")
    print(f"compile_cache dir={cache_dir}")
    import jax

    from repro.kernels import ops
    from repro.serve.query_server import QueryServer

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter.on_duration)
    jax.monitoring.register_event_listener(counter.on_event)

    t = time.perf_counter()
    ds = cell.generator.generate(cell.config["params"], args.seed)
    store = ds.load()
    print(f"store {json.dumps(ds.sizes)} build_s={time.perf_counter() - t}")
    server = QueryServer(store)
    traffic = Traffic(cell.mix, cell.queries, ds, args.seed)
    t = time.perf_counter()
    warmup = traffic.warmup()
    for req in warmup:
        server.execute(req.key, req.text)
    c0 = counter.snapshot()
    print(f"warmup requests={len(warmup)} s={time.perf_counter() - t} "
          f"compiles={c0[0]} cache_hits={c0[1]} compile_s={c0[2]}")
    window_requests = traffic.window()

    calls = trace_dir = None
    if traced:
        kernel_calls = devtrace.KernelCalls(ops, cell.costs(devtrace.kernels(ops)))
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        devtrace.start(trace_dir)
    setup_s = time.perf_counter() - t_start
    try:
        if traced:
            with kernel_calls:
                sent, window = serve(server, window_requests, args.seconds, True)
            jax.profiler.stop_trace()
            calls = kernel_calls.calls
        else:
            sent, window = serve(server, window_requests, args.seconds, False)
        c1 = counter.snapshot()
        print(f"window compiles={c1[0] - c0[0]} cache_hits={c1[1] - c0[1]} "
              f"compile_s={c1[2] - c0[2]} programs={counter.names[c0[0]:]}")
        device["memory_peak_bytes"] = peak_memory(dev["_device"])

        records, late, errors, answers, binds = [], [], [], [], []
        for req, t0, t1, res, error in sent:
            if error is not None:
                if t1 <= window[1]:
                    errors.append((req, error))
                continue
            spans = [(a, a + d) for a, d in (res.trace.span_bounds(s)
                                            for s in ("translate", "execute")) if d is not None]
            rec = Done(req.key, req.query, t0, t1, spans, res.kernel_dispatches, res.n_rows)
            if t1 > window[1]:
                late.append(rec)
                continue
            records.append(rec)
            answers.append((req.query, check.decode(store.dict, res.rows)))
            binds.append(req.bind)
        for line in per_query_lines(records):
            print(line)
        for req, tb in errors[:3]:
            print(f"request {req.key} raised:\n{tb}", file=sys.stderr)
        run = Run(records, window, setup_s, calls, late=late, mix=cell.mix["order"])
        if traced:
            trace = devtrace.read(trace_dir, [t0 for _, t0, _, _, _ in sent])
            shift, inside = devtrace.align(calls, trace.modules)
            print(f"trace align shift_s={shift} modules_in_calls={inside}/{len(trace.modules)}",
                  file=sys.stderr)
            run.device = trace.shifted(shift)
            devtrace.attribute(calls, run.device)
            run.peaks = cell.peaks(device["kind"])
            device["busy_s"] = stats.length(run.busy)
            device["window_s"] = run.window_s
            if kernel_calls.cost_errors:
                print(f"roofline costs unread: {kernel_calls.cost_errors}", file=sys.stderr)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # the program's state goes before the reference runs
    del server, store, sent
    gc.collect()
    reference = load_module(cell.reference_path).Reference(ds)
    t = time.perf_counter()
    correct, numbers = check.judge(answers, reference, cell.queries, binds,
                                   len(errors), cell.mix["limits"])
    print(f"check requests={len(answers)} reference_s={time.perf_counter() - t}")

    metrics = {}
    for m in cell.metrics(traced):
        value = cell.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for name, v in metrics.items():
        print(f"metric {name}={v['value']} {v['unit']}")
    result = {"correct": correct, "attempted": len(records) + len(errors),
              "failed": len(errors), "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = breakdown(run)
    result["check"] = numbers
    for name, n in numbers.items():
        print(f"check {name}={n['value']} limit={n['limit']}", file=sys.stderr)
    print(json.dumps(result))
    return 0


def breakdown(run: Run) -> dict:
    """The device ops that took most time, named by the kernel call that
    holds them, and the longest idle gaps, named by what the host was
    doing in them."""
    by_op: Dict[str, float] = {}
    for call in run.calls:
        for op, s in call.ops.items():
            by_op[f"{call.kernel}/{op}"] = by_op.get(f"{call.kernel}/{op}", 0.0) + s
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]

    gaps = sorted(stats.gaps(run.busy, *run.window), key=lambda g: g[0] - g[1])[:10]
    named = []
    for a, b in gaps:
        named.append([host_activity(run, a, b), b - a])
    return {"device_ops": [list(x) for x in device_ops], "idle_gaps": named}


def host_activity(run: Run, a: float, b: float) -> str:
    """What the host did for most of [a, b]: a kernel call's host side, the
    engine's operators or the planner inside a request, or the client."""
    share: Dict[str, float] = {}

    def add(name, intervals):
        share[name] = share.get(name, 0.0) + stats.length(stats.clip(intervals, a, b))

    for r in run.requests + run.late:
        if r.t1 < a or r.t0 > b:
            continue
        calls = [c for c in run.calls if r.t0 <= c.t0 <= r.t1 and c.t1 >= a and c.t0 <= b]
        inner = stats.union((c.t0, c.t1) for c in calls)
        for c in calls:
            add(f"dispatch.{c.kernel}@{r.query}", [(c.t0, c.t1)])
        spans = stats.union(r.spans)
        add(f"operators@{r.query}", stats.intersect(
            spans, stats.gaps(inner, r.t0, r.t1)))
        add(f"planner@{r.query}", stats.gaps(spans, r.t0, r.t1))
    covered = sum(share.values())
    share["client"] = max(0.0, (b - a) - covered)
    return max(share.items(), key=lambda kv: kv[1])[0]
