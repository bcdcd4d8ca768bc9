"""The program's own spans of each request of a traced run, read after the
window from the traces the server keeps of its recent requests
(``repro.core.telemetry.recent_traces``), each matched to the request
whose client-side interval holds its start.

A request's kernel dispatches are spans with parents (``hash_build`` →
``radix_partition``). ``phase_seconds`` cuts their time into phases and
charges each instant to the innermost dispatch that holds it: a
dispatch's self time is its span less its children's, and is ``launch``,
``wait`` or ``copy`` inside one of its device round trips, ``finish``
after its last round trip, and ``stage`` otherwise. A program without
these records (one older than them) reads as None, and raises nothing.
"""

from __future__ import annotations

import bisect
import collections
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from bench.harness import stats

PHASES = ("stage", "launch", "wait", "copy", "finish")


def window_traces(run, requests=None) -> Optional[List[Tuple[object, object]]]:
    """(request, its program trace) for each request of the window (or of
    ``requests``) whose trace the program still holds; None where it
    holds none."""
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    recent = getattr(telemetry, "recent_traces", None)
    if recent is None:
        return None
    traces = sorted(recent(), key=lambda t: t.t0)
    starts = [t.t0 for t in traces]
    out = []
    for r in run.requests if requests is None else requests:
        i = bisect.bisect_left(starts, r.t0)
        if i < len(traces) and traces[i].t0 <= r.t1:
            out.append((r, traces[i]))
    return out or None


def mean_per_request(run, value: Callable[[object], float]) -> Optional[float]:
    """Mean over the window's traced requests of ``value(trace)``."""
    pairs = window_traces(run)
    if pairs is None:
        return None
    return sum(value(tr) for _, tr in pairs) / len(pairs)


def span_seconds(trace, name: str) -> float:
    return sum(dur for n, _cat, _t0, dur, _args in trace.spans if n == name)


def _children(dispatches) -> Dict[int, List[stats.Interval]]:
    kids: Dict[int, List[stats.Interval]] = collections.defaultdict(list)
    for d in dispatches:
        if d.parent is not None:
            kids[d.parent].append((d.t0, d.t1))
    return kids


def phase_seconds(dispatches: Iterable) -> Dict[str, float]:
    """Seconds of the dispatches' self time in each phase."""
    dispatches = list(dispatches)
    kids = _children(dispatches)
    out = dict.fromkeys(PHASES, 0.0)
    for d in dispatches:
        launch = sum(b - a for a, b, _, _ in d.trips)
        wait = sum(c - b for _, b, c, _ in d.trips)
        copy = sum(e - c for _, _, c, e in d.trips)
        end = d.trips[-1][3] if d.trips else d.t1
        inner = kids.get(d.id, ())
        before = sum(b - a for a, b in inner if b <= end)
        after = sum(b - a for a, b in inner if b > end)
        out["stage"] += (end - d.t0) - before - launch - wait - copy
        out["launch"] += launch
        out["wait"] += wait
        out["copy"] += copy
        out["finish"] += (d.t1 - end) - after
    return out


def compile_seconds(trace) -> float:
    """Compile seconds the program charged inside the request: to its
    dispatches and to the request outside them."""
    return (sum(s for d in trace.dispatches for _, s, _ in d.compiles)
            + sum(s for _, s, _ in trace.compiles))


def labelled_intervals(trace, query: str) -> Dict[str, List[stats.Interval]]:
    """Each instant of a request's program spans under one name:
    ``<phase>.<kernel>@<query>`` in a dispatch's self time,
    ``parse@<query>``, ``plan@<query>`` (the plan-cache lookup with it),
    and ``operators@<query>`` for the engine's translate and execute
    spans outside every dispatch."""
    out: Dict[str, List[stats.Interval]] = collections.defaultdict(list)
    kids = _children(trace.dispatches)
    for d in trace.dispatches:
        inner = stats.union(kids.get(d.id, ()))

        def own(a: float, b: float, name: str) -> None:
            out[f"{name}.{d.kernel}@{query}"] += stats.gaps(inner, a, b)

        t = d.t0
        for a, b, c, e in d.trips:
            own(t, a, "stage")
            out[f"launch.{d.kernel}@{query}"].append((a, b))
            out[f"wait.{d.kernel}@{query}"].append((b, c))
            out[f"copy.{d.kernel}@{query}"].append((c, e))
            t = e
        own(t, d.t1, "finish" if d.trips else "stage")
    top = stats.union((d.t0, d.t1) for d in trace.dispatches if d.parent is None)
    for name, _cat, t0, dur, _args in trace.spans:
        if name in ("parse", "plan", "plan_cache"):
            out[f"{'parse' if name == 'parse' else 'plan'}@{query}"].append((t0, t0 + dur))
        elif name in ("translate", "execute"):
            out[f"operators@{query}"] += stats.gaps(top, t0, t0 + dur)
    return out


def idle_phases(run, n: int = 10) -> Optional[List[list]]:
    """The ``n`` longest gaps in which the device ran nothing, each as
    [name, seconds], named by the program span that covers most of it
    (``labelled_intervals``), or ``client`` where none covers most. The
    request that ends after the window counts too."""
    pairs = window_traces(run, run.requests + run.late)
    if pairs is None or not run.on_device:
        return None
    labelled = [(r, labelled_intervals(tr, r.query)) for r, tr in pairs]
    gaps = sorted(stats.gaps(run.busy, *run.window), key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        share: Dict[str, float] = {}
        for r, spans in labelled:
            if r.t1 < a or r.t0 > b:
                continue
            for name, intervals in spans.items():
                s = stats.length(stats.clip(intervals, a, b))
                if s > 0:
                    share[name] = share.get(name, 0.0) + s
        share["client"] = max(0.0, (b - a) - sum(share.values()))
        out.append([max(share.items(), key=lambda kv: kv[1])[0], b - a])
    return out
