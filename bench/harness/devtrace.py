"""The traced run: host intervals of every kernel call, taken around each
public kernel of ``repro.kernels.ops`` from the benchmark's own wrapper,
and the device's operations, read from the JAX profiler's trace. Both go
onto the host's ``perf_counter`` clock so the per-layer metrics can cut
one request's time into the layers below it."""

from __future__ import annotations

import bisect
import dataclasses
import glob
import inspect
import os
import statistics
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

from bench.harness import stats

PREFIX = "bench."
REQUEST = PREFIX + "request"
DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
# how far the device's clock in the trace may stand from the host's
MAX_SHIFT_S = 0.005


@dataclasses.dataclass
class Call:
    kernel: str
    t0: float
    t1: float
    cost: Optional[Tuple[float, float]]  # logical (ops, bytes), None if unknown
    device_s: float = 0.0  # device time of its own ops (nested calls excluded)
    ops: Dict[str, float] = dataclasses.field(default_factory=dict)


def kernels(ops) -> List[str]:
    """The public kernels of ``ops``: the functions its ledger decorator
    wrapped, each of which takes the ``backend`` it dispatches to."""
    return sorted(n for n, f in vars(ops).items()
                  if callable(getattr(f, "__wrapped__", None))
                  and "backend" in inspect.signature(f.__wrapped__).parameters)


class KernelCalls:
    """Context manager that wraps each public kernel of ``ops`` for the
    traced run: a ``jax.profiler.TraceAnnotation`` named after the kernel,
    the call's host interval, and its logical (ops, bytes) from ``costs``,
    each ``cost(result, *args, **kwargs)`` with the kernel's own
    arguments."""

    def __init__(self, ops, costs: Dict[str, Callable]):
        self.ops = ops
        self.costs = costs
        self.calls: List[Call] = []
        self.cost_errors: Dict[str, str] = {}
        self._saved: Dict[str, Callable] = {}

    def _wrap(self, name: str, fn: Callable) -> Callable:
        import jax

        cost_fn = self.costs.get(name)
        calls = self.calls

        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(PREFIX + name):
                result = fn(*args, **kwargs)
            call = Call(name, t0, time.perf_counter(), None)
            calls.append(call)
            if cost_fn is not None:
                try:
                    call.cost = tuple(float(x) for x in cost_fn(result, *args, **kwargs))
                except Exception as e:  # noqa: BLE001 - a cost it cannot read stays unknown
                    self.cost_errors[name] = repr(e)
            return result

        return wrapper

    def __enter__(self):
        for name in kernels(self.ops):
            self._saved[name] = getattr(self.ops, name)
            setattr(self.ops, name, self._wrap(name, self._saved[name]))
        return self

    def __exit__(self, *exc):
        for name, fn in self._saved.items():
            setattr(self.ops, name, fn)
        self._saved.clear()


@dataclasses.dataclass
class DeviceTrace:
    """Device operations as (start, end, name), and the executions of
    whole programs as (start, end), on the perf_counter clock."""

    ops: List[Tuple[float, float, str]]
    modules: List[Tuple[float, float]]
    n_devices: int

    def busy(self) -> List[stats.Interval]:
        return stats.union((a, b) for a, b, _ in self.ops)

    def shifted(self, d: float) -> "DeviceTrace":
        return DeviceTrace([(a + d, b + d, n) for a, b, n in self.ops],
                           [(a + d, b + d) for a, b in self.modules], self.n_devices)


def start(logdir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)


def op_name(text: str) -> str:
    """An HLO op's name from the trace's event text, which on the TPU is
    the whole instruction (``%name = type op(operands), ...``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def read(logdir: str, request_t0: List[float]) -> DeviceTrace:
    """Device ops of the trace in ``logdir``. The profiler's clock is put
    onto perf_counter by the request annotations, whose perf_counter
    starts the caller recorded in ``request_t0``."""
    import jax

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {logdir}, found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    marks: List[float] = []
    raw: List[Tuple[int, int, str]] = []
    modules: List[Tuple[int, int]] = []
    planes = []
    devices = set()
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == REQUEST:
                        marks.append(ev.start_ns)
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                planes.append(f"{plane.name}|{line.name}")
                if line.name == DEVICE_LINE:
                    devices.add(plane.name)
                    raw.extend((ev.start_ns, ev.end_ns, op_name(ev.name)) for ev in line.events)
                elif line.name == MODULE_LINE:
                    modules.extend((ev.start_ns, ev.end_ns) for ev in line.events)
    marks.sort()
    if len(marks) != len(request_t0) or not marks:
        raise RuntimeError(f"{len(marks)} request annotations in the trace, "
                           f"{len(request_t0)} requests sent")
    offset = statistics.median(t - m / 1e9 for t, m in zip(sorted(request_t0), marks))
    spread = max(abs(t - m / 1e9 - offset) for t, m in zip(sorted(request_t0), marks))
    print(f"trace clock offset_s={offset} worst_mark_gap_s={spread} "
          f"device_ops={len(raw)} modules={len(modules)} planes={sorted(set(planes))}",
          file=sys.stderr)
    return DeviceTrace([(a / 1e9 + offset, b / 1e9 + offset, name) for a, b, name in raw],
                       [(a / 1e9 + offset, b / 1e9 + offset) for a, b in modules],
                       len(devices))


def top_level(calls: List[Call]) -> List[Call]:
    """The calls that no other call holds, in order."""
    out: List[Call] = []
    for c in sorted(calls, key=lambda c: (c.t0, -c.t1)):
        if not out or c.t0 >= out[-1].t1:
            out.append(c)
    return out


def align(calls: List[Call], modules: List[Tuple[float, float]],
          max_shift: float = MAX_SHIFT_S) -> Tuple[float, int]:
    """The shift, within ``max_shift``, to add to the device's times so
    that the most program executions lie inside a kernel call's host
    interval (each call waits for its results, so its programs run inside
    it), and how many then do. The profiler puts device and host events
    on one clock only to within some tenths of a millisecond, which is
    longer than many kernel calls."""
    top = top_level(calls)
    starts = [c.t0 for c in top]
    edges: List[Tuple[float, int]] = []
    for a, b in modules:
        lo = max(bisect.bisect_left(starts, a - max_shift) - 1, 0)
        hi = bisect.bisect_right(starts, b + max_shift)
        for c in top[lo:hi]:
            d0, d1 = c.t0 - a, c.t1 - b  # shifts that put [a, b] inside c
            if d0 <= d1 and d1 >= -max_shift and d0 <= max_shift:
                edges += [(max(d0, -max_shift), 0), (min(d1, max_shift), 1)]
    best, best_at, n = 0, (0.0, 0.0), 0
    edges.sort()
    for i, (x, kind) in enumerate(edges):
        if kind == 0:
            n += 1
            if n > best:
                best = n
                nxt = next(x2 for x2, k2 in edges[i + 1:] if k2 == 1)
                best_at = (x, nxt)
        else:
            n -= 1
    return (best_at[0] + best_at[1]) / 2, best


def attribute(calls: List[Call], trace: DeviceTrace) -> List[Tuple[float, float, str, Optional[int]]]:
    """Give each device op of ``trace`` (aligned with ``align``) to the
    innermost kernel call whose host interval holds its start, and add
    its time to that call; returns the ops with the index of their call
    (None outside every call)."""
    order = sorted(range(len(calls)), key=lambda i: (calls[i].t0, -calls[i].t1))
    out = []
    stack: List[int] = []
    j = 0
    for a, b, name in sorted(trace.ops):
        while j < len(order) and calls[order[j]].t0 <= a:
            stack.append(order[j])
            j += 1
        while stack and calls[stack[-1]].t1 < a:
            stack.pop()
        # an enclosing call may still hold ``a`` under a finished inner one
        owner = next((i for i in reversed(stack) if calls[i].t0 <= a <= calls[i].t1), None)
        if owner is not None:
            c = calls[owner]
            c.device_s += b - a
            c.ops[name] = c.ops.get(name, 0.0) + (b - a)
        out.append((a, b, name, owner))
    return out
