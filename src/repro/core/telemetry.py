"""Query-scoped telemetry (DESIGN.md §13).

The paper chose vectorization over code generation because the operator
tree stays observable (§3.1). This module makes that observability
*query-scoped* instead of process-global, so a server interleaving many
queries through one Engine can attribute every kernel dispatch, span and
buffer to exactly one request:

  KernelLedger   — dispatch counts and wall seconds keyed by kernel name
                   and by (kernel, backend). One process-global instance
                   backs ``kernels.ops.DISPATCH_COUNTS`` (its ``counts``
                   Counter IS that object); one per-query instance lives
                   on each QueryTrace.
  QueryTrace     — span recorder for the query lifecycle (parse → plan →
                   translate → execute), a per-query KernelLedger, a
                   per-dispatch kernel event log, the rows and key
                   seeks of SIP-consuming scans (``count_sip_scan``), and
                   the rows join operators emitted with their summed
                   weights (``count_join_rows``).
                   Exports Chrome-trace
                   JSON (``chrome-tracing`` / Perfetto ``traceEvents``
                   format) so traces open directly in ui.perfetto.dev.
  trace_query()  — contextvar scope installing a QueryTrace as the active
                   attribution target. Kernel dispatches recorded while a
                   trace is active land in BOTH the trace's ledger and
                   the process-global one — the global ledger keeps its
                   "since process start / last reset" semantics for
                   existing callers, the scoped ledger gives exact
                   per-query attribution even under interleaving.
  Dispatch       — one kernel dispatch of a traced request as a span: its
                   id, its parent dispatch's id, the request's trace id,
                   start and end, the device round trips it made (launch,
                   wait, copy), host→device, device→host and padding
                   bytes, and the compiles charged to it. ``phases()``
                   cuts its self time into stage / launch / wait / copy /
                   finish (DESIGN.md §13).
  CompileLedger  — programs compiled (or loaded from the persistent
                   cache) in the process, each named by the program, the
                   kernel whose dispatch caused it and its input shapes.

PR 8 adds the workload-history primitives (DESIGN.md §14):

  query_fingerprint()    — canonical sha256 template key over the parsed
                           algebra: literals and instantiated entity
                           constants normalize to typed placeholders,
                           variables to first-appearance indices, so the
                           template instances of BSBM-style traffic share
                           one key regardless of spelling.
  CardinalityFeedback    — per-plan-node observed cardinalities keyed by
                           the planner's stable node fingerprint. The
                           executor records actual row counts after each
                           drain; the planner (EngineConfig.
                           cardinality_feedback="apply") overrides its
                           estimates with the observed history.

Only stdlib is imported here at module scope: ``kernels.ops`` imports
this module, so it must never (transitively) import the kernels package.
The fingerprint walkers lazily import ``repro.core.algebra`` inside the
function bodies for the same reason.
"""

from __future__ import annotations

import collections
import hashlib
import itertools
import json
import time
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Deque, Dict, List, Optional, Tuple


class KernelLedger:
    """Dispatch counts + wall-time for one attribution scope.

    Wall times are *inclusive* per public kernel wrapper: ``hash_build``
    internally dispatches ``radix_partition``, so both entries tick and
    the build's seconds include the partition's (same convention as the
    operator tree's self+children wall_time).
    """

    __slots__ = ("counts", "wall_s", "backend_counts", "backend_wall_s")

    def __init__(self, counts: Optional[collections.Counter] = None) -> None:
        # ``counts`` may be an externally owned Counter (kernels.ops keeps
        # DISPATCH_COUNTS' identity by handing it in here)
        self.counts: collections.Counter = (
            collections.Counter() if counts is None else counts
        )
        self.wall_s: Dict[str, float] = collections.defaultdict(float)
        self.backend_counts: collections.Counter = collections.Counter()
        self.backend_wall_s: Dict[Tuple[str, str], float] = collections.defaultdict(
            float
        )

    def record(self, name: str, backend: str, dt: float) -> None:
        self.counts[name] += 1
        self.wall_s[name] += dt
        self.backend_counts[(name, backend)] += 1
        self.backend_wall_s[(name, backend)] += dt

    def merge(self, other: "KernelLedger") -> None:
        """Accumulate another ledger (serving metrics aggregate request
        ledgers into a server-lifetime one)."""
        self.counts.update(other.counts)
        for k, v in other.wall_s.items():
            self.wall_s[k] += v
        self.backend_counts.update(other.backend_counts)
        for k, v in other.backend_wall_s.items():
            self.backend_wall_s[k] += v

    def total(self) -> int:
        return sum(self.counts.values())

    def total_wall_s(self) -> float:
        return sum(self.wall_s.values())

    def clear(self) -> None:
        self.counts.clear()
        self.wall_s.clear()
        self.backend_counts.clear()
        self.backend_wall_s.clear()

    def snapshot(self) -> dict:
        """JSON-able view: per-kernel counts/ms plus the per-backend
        breakdown keyed ``kernel/backend``."""
        return {
            "dispatches": dict(self.counts),
            "wall_ms": {k: round(v * 1e3, 4) for k, v in self.wall_s.items()},
            "by_backend": {
                f"{n}/{b}": c for (n, b), c in sorted(self.backend_counts.items())
            },
            "by_backend_wall_ms": {
                f"{n}/{b}": round(v * 1e3, 4)
                for (n, b), v in sorted(self.backend_wall_s.items())
            },
        }


# process-global fallback ledger — kernels.ops aliases its ``counts`` as
# DISPATCH_COUNTS, keeping the pre-§13 module API intact
_GLOBAL_LEDGER = KernelLedger()

_ACTIVE_TRACE: "ContextVar[Optional[QueryTrace]]" = ContextVar(
    "repro_active_trace", default=None
)
_ACTIVE_DISPATCH: "ContextVar[Optional[Dispatch]]" = ContextVar(
    "repro_active_dispatch", default=None
)

# ids of traces and dispatches, unique in the process
_IDS = itertools.count(1)

PHASES = ("stage", "launch", "wait", "copy", "finish")


class Dispatch:
    """One kernel dispatch of a traced request, as a span.

    ``trips`` holds each device round trip the dispatch made
    (``kernels.tiling.round_trip``) as perf_counter instants (start,
    launched, waited, copied): ``launch`` is the jitted call (argument
    transfer, dispatch and any compile), ``wait`` the block until the
    outputs are ready, ``copy`` their copy to host numpy. ``kids`` holds
    the (start, end) of each child dispatch (``hash_build`` →
    ``radix_partition``), whose time is theirs and not this one's."""

    __slots__ = ("id", "parent", "trace_id", "kernel", "backend", "t0", "t1",
                 "trips", "kids", "h2d_bytes", "d2h_bytes", "pad_logical_bytes",
                 "pad_bytes", "compiles", "cache_hits", "inputs")

    def __init__(self, kernel: str, backend: str, trace_id: int,
                 parent: Optional[int] = None) -> None:
        self.id = next(_IDS)
        self.parent = parent
        self.trace_id = trace_id
        self.kernel = kernel
        self.backend = backend
        self.t0 = self.t1 = 0.0
        self.trips: List[Tuple[float, float, float, float]] = []
        self.kids: List[Tuple[float, float]] = []
        # host numpy arguments handed to the device / outputs copied back
        self.h2d_bytes = self.d2h_bytes = 0
        # bytes before and after ``kernels.tiling.pad``
        self.pad_logical_bytes = self.pad_bytes = 0
        # (program, seconds, input shapes) of each compile it caused
        self.compiles: List[Tuple[str, float, tuple]] = []
        self.cache_hits = 0
        # the round trip in flight's arguments, whose shapes a compile names
        self.inputs: tuple = ()

    def add_trip(self, t0: float, t1: float, t2: float, t3: float,
                 h2d_bytes: int, d2h_bytes: int) -> None:
        self.trips.append((t0, t1, t2, t3))
        self.h2d_bytes += h2d_bytes
        self.d2h_bytes += d2h_bytes

    def phases(self) -> Dict[str, float]:
        """Self time (the span less its child dispatches) in seconds by
        phase: ``launch``, ``wait`` and ``copy`` of its round trips;
        ``finish`` after its last round trip; ``stage`` the rest. With no
        round trip (numpy plane, empty-input shortcuts) it is all
        ``stage``."""
        launch = wait = copy = 0.0
        for a, b, c, e in self.trips:
            launch += b - a
            wait += c - b
            copy += e - c
        end = self.trips[-1][3] if self.trips else self.t1
        before = sum(b - a for a, b in self.kids if b <= end)
        after = sum(b - a for a, b in self.kids if b > end)
        return {
            "stage": (end - self.t0) - before - launch - wait - copy,
            "launch": launch,
            "wait": wait,
            "copy": copy,
            "finish": (self.t1 - end) - after,
        }


class CompileLedger:
    """Programs compiled in the process, fresh or loaded from JAX's
    persistent cache (both end in one ``backend_compile_duration`` event;
    a load also raises ``cache_hits``). Each event is (kernel, program,
    seconds, input shapes); the kernel is the dispatch that caused it,
    None outside one."""

    __slots__ = ("programs", "cache_hits", "seconds", "events")

    def __init__(self) -> None:
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.events: List[Tuple[Optional[str], str, float, tuple]] = []

    def snapshot(self) -> Tuple[int, int, float]:
        """(programs, cache hits, compile seconds) so far."""
        return self.programs, self.cache_hits, self.seconds


_COMPILES = CompileLedger()

# the last request traces the server finished, newest last: what the
# process can still say about its recent requests after the server that
# served them is gone. Bounded, as a trace holds every dispatch span:
# ~0.3 MB for a BSBM explore request's ~440 dispatches.
RECENT_TRACES = 128
_RECENT: Deque["QueryTrace"] = collections.deque(maxlen=RECENT_TRACES)


def global_ledger() -> KernelLedger:
    return _GLOBAL_LEDGER


def compile_ledger() -> CompileLedger:
    """The process-global compile ledger (fed by the kernels package's
    ``jax.monitoring`` listener from the first device dispatch on)."""
    return _COMPILES


def current_trace() -> Optional["QueryTrace"]:
    """The QueryTrace installed for the current context, if any."""
    return _ACTIVE_TRACE.get()


def current_dispatch() -> Optional[Dispatch]:
    """The innermost kernel dispatch in flight under the active trace."""
    return _ACTIVE_DISPATCH.get()


def retain(trace: "QueryTrace") -> None:
    """Keep a finished request's trace among the recent ones."""
    _RECENT.append(trace)


def recent_traces() -> List["QueryTrace"]:
    """The last ``RECENT_TRACES`` finished request traces, oldest first."""
    return list(_RECENT)


def record_dispatch(name: str, backend: str, t0: float, dt: float) -> None:
    """Attribute one kernel dispatch timed by the caller: to the active
    query trace when one is installed, and always to the process-global
    ledger."""
    tr = _ACTIVE_TRACE.get()
    if tr is not None:
        tr.ledger.record(name, backend, dt)
        if tr.kernel_events:
            parent = _ACTIVE_DISPATCH.get()
            d = Dispatch(name, backend, tr.id, None if parent is None else parent.id)
            d.t0, d.t1 = t0, t0 + dt
            tr.dispatches.append(d)
    _GLOBAL_LEDGER.record(name, backend, dt)


def open_dispatch(tr: "QueryTrace", kernel: str, backend: str):
    """Start a dispatch span under ``tr``, child of the dispatch in flight;
    returns it with the token ``close_dispatch`` takes."""
    parent = _ACTIVE_DISPATCH.get()
    d = Dispatch(kernel, backend, tr.id, None if parent is None else parent.id)
    token = _ACTIVE_DISPATCH.set(d)
    d.t0 = time.perf_counter()
    return d, token


def close_dispatch(tr: "QueryTrace", d: Dispatch, token) -> None:
    """End ``d``: charge its span to its parent as child time, and record
    it in the trace's and the process-global ledgers."""
    d.t1 = time.perf_counter()
    _ACTIVE_DISPATCH.reset(token)
    parent = _ACTIVE_DISPATCH.get()
    if parent is not None:
        parent.kids.append((d.t0, d.t1))
    dt = d.t1 - d.t0
    tr.ledger.record(d.kernel, d.backend, dt)
    if tr.kernel_events:
        tr.dispatches.append(d)
    _GLOBAL_LEDGER.record(d.kernel, d.backend, dt)


def count_sip_scan(rows: int, seeks: int = 0) -> None:
    """Charge rows an index scan read from the store while consuming a SIP
    filter, and the key seeks it took, to the active request's trace."""
    tr = _ACTIVE_TRACE.get()
    if tr is not None:
        tr.sip_scan_rows += rows
        tr.sip_seeks += seeks


def count_join_rows(batch, rows: int) -> None:
    """Charge the ``rows`` active rows of a batch a join operator emitted,
    and the Σ of their weights (the solutions they stand for under weighted
    counting, DESIGN.md §17), to the active request's trace."""
    tr = _ACTIVE_TRACE.get()
    if tr is not None:
        tr.join_rows += rows
        tr.join_weight += batch.weight_total(rows)


def record_compile(program: str, seconds: float) -> None:
    """Charge one compile to the dispatch in flight (with its inputs'
    shapes), else to the active request, and always to the process-global
    compile ledger."""
    d = _ACTIVE_DISPATCH.get()
    shapes = () if d is None else tuple(tuple(getattr(a, "shape", ())) for a in d.inputs)
    if d is not None:
        d.compiles.append((program, seconds, shapes))
    else:
        tr = _ACTIVE_TRACE.get()
        if tr is not None:
            tr.compiles.append((program, seconds, shapes))
    _COMPILES.programs += 1
    _COMPILES.seconds += seconds
    _COMPILES.events.append((None if d is None else d.kernel, program, seconds, shapes))


def record_cache_hit() -> None:
    """Count one program loaded from the persistent compilation cache."""
    d = _ACTIVE_DISPATCH.get()
    if d is not None:
        d.cache_hits += 1
    else:
        tr = _ACTIVE_TRACE.get()
        if tr is not None:
            tr.cache_hits += 1
    _COMPILES.cache_hits += 1


@contextmanager
def trace_query(label: str = "query", trace: Optional["QueryTrace"] = None):
    """Install ``trace`` (or a fresh QueryTrace labelled ``label``) as the
    active attribution scope."""
    tr = trace if trace is not None else QueryTrace(label)
    token = _ACTIVE_TRACE.set(tr)
    try:
        yield tr
    finally:
        _ACTIVE_TRACE.reset(token)


# Perfetto renders one horizontal lane per (pid, tid); we use three fixed
# lanes: query-lifecycle spans, kernel dispatches, operator tree.
_TID_QUERY, _TID_KERNELS, _TID_OPERATORS = 1, 2, 3


class QueryTrace:
    """Span + kernel-event recorder for one query execution."""

    def __init__(self, label: str = "query", kernel_events: bool = True) -> None:
        self.id = next(_IDS)
        self.label = label
        self.kernel_events = kernel_events
        self.ledger = KernelLedger()
        self.t0 = time.perf_counter()
        # (name, category, start_s, dur_s, args) — start in perf_counter time
        self.spans: List[Tuple[str, str, float, float, dict]] = []
        # every kernel dispatch, in the order they ended (children first)
        self.dispatches: List[Dispatch] = []
        # compiles outside any dispatch: (program, seconds, input shapes)
        self.compiles: List[Tuple[str, float, tuple]] = []
        self.cache_hits = 0
        # rows read by index scans consuming a SIP filter, and the scans
        # that sought the build keys' runs (``count_sip_scan``)
        self.sip_scan_rows = 0
        self.sip_seeks = 0
        # rows join operators emitted, and Σ of their weights
        # (``count_join_rows``)
        self.join_rows = 0
        self.join_weight = 0
        # (label, depth, start_s, dur_s, args) — synthesized operator lane
        self._operators: List[Tuple[str, float, float, dict]] = []

    def phase_totals(self) -> Dict[str, float]:
        """Seconds of the request's dispatch self time in each phase."""
        out = dict.fromkeys(PHASES, 0.0)
        for d in self.dispatches:
            for k, v in d.phases().items():
                out[k] += v
        return out

    # -- recording ----------------------------------------------------------

    @contextmanager
    def span(self, name: str, cat: str = "query", **args):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.spans.append((name, cat, t0, time.perf_counter() - t0, args))

    def add_span(self, name: str, cat: str, t0: float, dur: float, **args) -> None:
        """Record an externally timed span (perf_counter timebase)."""
        self.spans.append((name, cat, t0, dur, args))

    def span_bounds(self, name: str) -> Optional[Tuple[float, float]]:
        for n, _cat, t0, dur, _a in self.spans:
            if n == name:
                return t0, dur
        return None

    def add_operator_tree(self, root, start: Optional[float] = None) -> None:
        """Synthesize the operator lane from the tree's post-hoc OpStats:
        each operator becomes one complete event whose duration is its
        inclusive wall_time, children laid out sequentially inside the
        parent's window (wall_time is self+children, so they nest)."""
        if start is None:
            bounds = self.span_bounds("execute")
            start = bounds[0] if bounds else self.t0

        def walk(op, t: float) -> None:
            s = op.stats
            args = {"results": s.results, "next_calls": s.next_calls}
            if getattr(s, "est_rows", None) is not None:
                args["est_rows"] = round(float(s.est_rows), 1)
            self._operators.append((f"{s.name}{s.detail}", t, s.wall_time, args))
            tc = t
            for c in op.children():
                walk(c, tc)
                tc += c.stats.wall_time

        walk(root, start)

    # -- export -------------------------------------------------------------

    def _us(self, t: float) -> float:
        return (t - self.t0) * 1e6

    def chrome_events(self) -> List[dict]:
        ev: List[dict] = []
        for tid, name in (
            (_TID_QUERY, "query"),
            (_TID_KERNELS, "kernels"),
            (_TID_OPERATORS, "operators"),
        ):
            ev.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": 1,
                    "tid": tid,
                    "args": {"name": name},
                }
            )
        for name, cat, t0, dur, args in self.spans:
            ev.append(
                {
                    "name": name,
                    "cat": cat,
                    "ph": "X",
                    "ts": self._us(t0),
                    "dur": dur * 1e6,
                    "pid": 1,
                    "tid": _TID_QUERY,
                    "args": dict(args),
                }
            )
        for d in self.dispatches:
            ev.append(self._kernel_event(d.kernel, "kernel", d.t0, d.t1, {
                "backend": d.backend, "id": d.id, "parent": d.parent,
                "trace": d.trace_id, "h2d_bytes": d.h2d_bytes,
                "d2h_bytes": d.d2h_bytes, "pad_logical_bytes": d.pad_logical_bytes,
                "pad_bytes": d.pad_bytes, "cache_hits": d.cache_hits,
                "compiles": [[p, s] for p, s, _ in d.compiles],
                "self_ms": {k: v * 1e3 for k, v in d.phases().items()},
            }))
            ev.extend(self._phase_events(d))
        end = max((t0 + dur for _n, _c, t0, dur, _a in self.spans), default=self.t0)
        ev.append({"name": "sip_scan", "ph": "C", "ts": self._us(end), "pid": 1,
                   "args": {"sip_scan_rows": self.sip_scan_rows,
                            "sip_seeks": self.sip_seeks}})
        ev.append({"name": "join", "ph": "C", "ts": self._us(end), "pid": 1,
                   "args": {"join_rows": self.join_rows,
                            "join_weight": self.join_weight}})
        for label, t0, dur, args in self._operators:
            ev.append(
                {
                    "name": label,
                    "cat": "operator",
                    "ph": "X",
                    "ts": self._us(t0),
                    "dur": dur * 1e6,
                    "pid": 1,
                    "tid": _TID_OPERATORS,
                    "args": dict(args),
                }
            )
        return ev

    def _kernel_event(self, name: str, cat: str, t0: float, t1: float,
                      args: dict) -> dict:
        return {"name": name, "cat": cat, "ph": "X", "ts": self._us(t0),
                "dur": (t1 - t0) * 1e6, "pid": 1, "tid": _TID_KERNELS,
                "args": args}

    def _phase_events(self, d: Dispatch) -> List[dict]:
        """A dispatch's phases as events nested in its own: ``stage``
        before and between its round trips, each trip's ``launch``,
        ``wait`` and ``copy``, and ``finish`` after the last (stage and
        finish events hold any child dispatch in their interval)."""
        ev = []
        t = d.t0
        for a, b, c, e in d.trips:
            if a > t:
                ev.append(self._kernel_event("stage", "phase", t, a, {"kernel": d.kernel}))
            for name, x, y in (("launch", a, b), ("wait", b, c), ("copy", c, e)):
                ev.append(self._kernel_event(name, "phase", x, y, {"kernel": d.kernel}))
            t = e
        name = "finish" if d.trips else "stage"
        if d.t1 > t:
            ev.append(self._kernel_event(name, "phase", t, d.t1, {"kernel": d.kernel}))
        return ev

    def to_chrome_trace(self) -> dict:
        """The chrome://tracing / Perfetto ``traceEvents`` document."""
        return {
            "traceEvents": self.chrome_events(),
            "displayTimeUnit": "ms",
            "otherData": {"query": self.label},
        }

    def chrome_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_chrome_trace(), indent=indent)

    def save_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.chrome_json())

    def summary(self) -> dict:
        """Compact JSON-able digest: span durations, the kernel ledger, and
        the dispatches' self time by phase and their byte counts."""
        return {
            "query": self.label,
            "spans_ms": {
                name: round(dur * 1e3, 4) for name, _c, _t, dur, _a in self.spans
            },
            "kernels": self.ledger.snapshot(),
            "phases_ms": {k: round(v * 1e3, 4) for k, v in self.phase_totals().items()},
            "h2d_bytes": sum(d.h2d_bytes for d in self.dispatches),
            "d2h_bytes": sum(d.d2h_bytes for d in self.dispatches),
            "sip_scan_rows": self.sip_scan_rows,
            "sip_seeks": self.sip_seeks,
            "join_rows": self.join_rows,
            "join_weight": self.join_weight,
        }


# ---------------------------------------------------------------------------
# query fingerprinting (DESIGN.md §14)
# ---------------------------------------------------------------------------

# Term classification for placeholder normalization. Terms are
# str | int | float (repro.core.dictionary.Term): quoted strings are RDF
# literals, everything else stringy is an IRI/prefixed name.


def _term_class(term) -> str:
    if isinstance(term, bool) or isinstance(term, (int, float)):
        return "<num>"
    if isinstance(term, str) and term.startswith('"'):
        return "<str>"
    return "<iri>"


def canonical_var_map(node) -> Dict[int, int]:
    """Variable id -> canonical index by first appearance in a pre-order
    walk of the logical algebra. Two spellings of the same template get
    identical maps, so fingerprints (template and node) are independent
    of parser-assigned variable ids."""
    order: Dict[int, int] = {}

    def visit(vid: int) -> None:
        if vid not in order:
            order[vid] = len(order)

    for tok in _algebra_tokens(node, canon=None, on_var=visit):
        pass
    return order


def _algebra_tokens(node, canon: Optional[Dict[int, int]], on_var=None):
    """Token stream over the logical algebra: structure tags, canonical
    variables, kept IRI constants in predicate position, and typed
    placeholders for instantiated constants. ``canon=None`` emits raw var
    ids (used while *building* the canonical map); ``on_var`` observes
    every variable in pre-order."""
    from repro.core import algebra as A

    def var_tok(vid: int) -> str:
        if on_var is not None:
            on_var(vid)
        return f"?{vid if canon is None else canon.get(vid, vid)}"

    def slot_tok(sl, keep: bool) -> str:
        if isinstance(sl, A.V):
            return var_tok(sl.id)
        return f"K:{sl.term}" if keep else _term_class(sl.term)

    def expr_toks(e):
        if e is None:
            return
        if isinstance(e, A.VarRef):
            yield var_tok(e.var)
        elif isinstance(e, A.Lit):
            yield _term_class(e.value)
        elif isinstance(e, A.Cmp):
            yield f"cmp:{e.op}("
            yield from expr_toks(e.lhs)
            yield from expr_toks(e.rhs)
            yield ")"
        elif isinstance(e, A.Arith):
            yield f"arith:{e.op}("
            yield from expr_toks(e.lhs)
            yield from expr_toks(e.rhs)
            yield ")"
        elif isinstance(e, (A.And, A.Or)):
            yield ("and(" if isinstance(e, A.And) else "or(")
            for t in e.terms:
                yield from expr_toks(t)
            yield ")"
        elif isinstance(e, A.Not):
            yield "not("
            yield from expr_toks(e.term)
            yield ")"
        elif isinstance(e, A.Bound):
            yield f"bound({var_tok(e.var)})"
        elif isinstance(e, A.Func):
            yield f"func:{e.name}("
            for a in e.args:
                yield from expr_toks(a)
            yield ")"
        else:
            yield f"expr:{type(e).__name__}"

    def pattern_toks(p):
        if isinstance(p, A.PathPattern):
            from repro.core.paths.expr import path_repr

            yield "PATH("
            yield slot_tok(p.s, keep=False)
            yield path_repr(p.expr)
            yield slot_tok(p.o, keep=False)
            yield ")"
            return
        yield "TP("
        yield slot_tok(p.s, keep=False)
        # the predicate defines the template's structure; subjects and
        # objects are the instantiated entities that vary per instance
        yield slot_tok(p.p, keep=True)
        yield slot_tok(p.o, keep=False)
        if p.g is not None:
            yield slot_tok(p.g, keep=True)
        if p.path:
            yield f"path:{p.path}"
        yield ")"

    def walk(n):
        if isinstance(n, A.BGP):
            yield "BGP("
            for p in n.patterns:
                yield from pattern_toks(p)
            yield ")"
        elif isinstance(n, A.Filter):
            yield "FILTER("
            yield from expr_toks(n.expr)
            yield from walk(n.child)
            yield ")"
        elif isinstance(n, (A.Join, A.Minus, A.NotExists, A.Union)):
            yield f"{type(n).__name__.upper()}("
            yield from walk(n.left)
            yield from walk(n.right)
            yield ")"
        elif isinstance(n, A.LeftJoin):
            yield "LEFTJOIN("
            yield from walk(n.left)
            yield from walk(n.right)
            yield from expr_toks(n.expr)
            yield ")"
        elif isinstance(n, A.Extend):
            yield f"BIND({var_tok(n.var)}"
            yield from expr_toks(n.expr)
            yield from walk(n.child)
            yield ")"
        elif isinstance(n, A.Project):
            yield "PROJECT("
            for v in n.vars:
                yield var_tok(v)
            yield from walk(n.child)
            yield ")"
        elif isinstance(n, A.Distinct):
            yield "DISTINCT("
            yield from walk(n.child)
            yield ")"
        elif isinstance(n, A.GroupAgg):
            yield "GROUP("
            for v in n.group_vars:
                yield var_tok(v)
            for a in n.aggs:
                mod = "distinct " if a.distinct else ""
                av = var_tok(a.var) if a.var is not None else "*"
                yield f"agg:{mod}{a.func}({av})->{var_tok(a.out)}"
            yield from walk(n.child)
            yield from expr_toks(n.having)
            yield ")"
        elif isinstance(n, A.OrderBy):
            yield "ORDERBY("
            for k in n.keys:
                yield f"{var_tok(k.var)}:{'asc' if k.ascending else 'desc'}"
            yield from walk(n.child)
            yield ")"
        elif isinstance(n, A.Slice):
            yield f"SLICE({n.limit}:{n.offset}"
            yield from walk(n.child)
            yield ")"
        else:
            yield f"NODE:{type(n).__name__}"

    yield from walk(node)


def query_fingerprint(node) -> str:
    """Canonical sha256 template key over a parsed logical plan: literals
    and instantiated subject/object constants become typed placeholders,
    variables become first-appearance indices, whitespace never enters.
    Instances of one query template share a fingerprint."""
    canon = canonical_var_map(node)
    toks = list(_algebra_tokens(node, canon=canon))
    return hashlib.sha256("\x1f".join(toks).encode()).hexdigest()


# ---------------------------------------------------------------------------
# cardinality feedback store (DESIGN.md §14)
# ---------------------------------------------------------------------------


class CardinalityFeedback:
    """Observed per-plan-node cardinalities keyed by the planner's stable
    node fingerprint (planner.annotate_fingerprints).

    The executor records each operator's actual output rows after a full
    drain; estimates decay toward recent observations through an EWMA so
    data drift is tracked without unbounded history. ``version`` bumps on
    every record — plan caches fold it into their key under
    ``cardinality_feedback="apply"`` so a repeated query re-plans against
    fresh history instead of serving the stale shape.

    Lives in core (stdlib-only) because the Planner consults it; the
    serving layer's WorkloadRepository owns and persists one."""

    __slots__ = ("alpha", "max_entries", "version", "_obs")

    def __init__(self, alpha: float = 0.5, max_entries: int = 4096) -> None:
        self.alpha = alpha
        self.max_entries = max_entries
        self.version = 0
        # node_fp -> [ewma_rows, n_observations]
        self._obs: Dict[str, List[float]] = {}

    def __len__(self) -> int:
        return len(self._obs)

    def record(self, node_fp: str, actual_rows: float) -> None:
        if not node_fp:
            return
        e = self._obs.get(node_fp)
        if e is None:
            if len(self._obs) >= self.max_entries:
                # bounded store: evict the least-observed fingerprint
                drop = min(self._obs, key=lambda k: self._obs[k][1])
                del self._obs[drop]
            self._obs[node_fp] = [float(actual_rows), 1]
        else:
            e[0] += self.alpha * (float(actual_rows) - e[0])
            e[1] += 1
        self.version += 1

    def lookup(self, node_fp: str) -> Optional[float]:
        e = self._obs.get(node_fp)
        return e[0] if e is not None else None

    def observations(self, node_fp: str) -> int:
        e = self._obs.get(node_fp)
        return int(e[1]) if e is not None else 0

    def snapshot(self) -> dict:
        """JSON-able state: {node_fp: [ewma_rows, n]}."""
        return {k: [round(v[0], 3), int(v[1])] for k, v in self._obs.items()}

    def merge(self, state: Dict[str, List[float]]) -> None:
        """Merge a persisted snapshot: existing entries combine by
        observation-count-weighted average (load order must not matter
        more than sample counts do)."""
        for fp, (rows, n) in state.items():
            n = max(int(n), 1)
            e = self._obs.get(fp)
            if e is None:
                if len(self._obs) >= self.max_entries:
                    drop = min(self._obs, key=lambda k: self._obs[k][1])
                    del self._obs[drop]
                self._obs[fp] = [float(rows), n]
            else:
                tot = e[1] + n
                e[0] = (e[0] * e[1] + float(rows) * n) / tot
                e[1] = tot
            self.version += 1
