"""What one run hands to the metric readers (``bench/metrics/<family>.py``):
the requests the window completed, the set-up time, and in a traced run
the kernel calls and device ops on one clock, with each request's time
cut into the layers below it."""

from __future__ import annotations

import bisect
import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness import stats
from bench.harness.devtrace import Call, DeviceTrace


@dataclasses.dataclass
class Done:
    """A request that completed inside the window."""

    key: str
    query: str
    t0: float
    t1: float
    spans: List[stats.Interval]  # the engine's translate and execute spans
    dispatches: int
    rows: int = 0

    @property
    def latency_s(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    requests: List[Done]
    window: Tuple[float, float]
    setup_s: float
    calls: Optional[List[Call]] = None
    device: Optional[DeviceTrace] = None
    peaks: Optional[dict] = None
    late: List[Done] = dataclasses.field(default_factory=list)  # ended after the window
    mix: Sequence[str] = ()  # the queries of one query mix, a query listed k times weighs k

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def traced(self) -> bool:
        return self.device is not None

    @property
    def on_device(self) -> bool:
        """Whether the trace holds a device's operations to read."""
        return self.traced and self.device.n_devices > 0

    @functools.cached_property
    def busy(self) -> List[stats.Interval]:
        return stats.clip(self.device.busy(), *self.window)

    @functools.cached_property
    def layers(self) -> List[Dict[str, float]]:
        """Per request, seconds in each layer, which together make up its
        latency: ``host`` (outside the engine's spans), ``operator`` (in
        the spans, outside kernel calls), ``dispatch_host`` (in kernel
        calls, device idle) and ``device`` (in kernel calls, device
        busy)."""
        calls = sorted(self.calls, key=lambda c: c.t0)
        starts = [c.t0 for c in calls]
        busy_starts = [a for a, _ in self.busy]
        busy_ends = [b for _, b in self.busy]
        out = []
        for r in self.requests:
            req = [(r.t0, r.t1)]
            lo, hi = bisect.bisect_left(starts, r.t0), bisect.bisect_right(starts, r.t1)
            w = stats.clip([(c.t0, c.t1) for c in calls[lo:hi]], r.t0, r.t1)
            s = stats.clip(r.spans, r.t0, r.t1)
            near = self.busy[bisect.bisect_left(busy_ends, r.t0):
                             bisect.bisect_right(busy_starts, r.t1)]
            d = stats.length(stats.intersect(w, stats.intersect(near, req)))
            out.append({
                "host": r.latency_s - stats.length(s),
                "operator": stats.length(s) - stats.length(stats.intersect(s, w)),
                "dispatch_host": stats.length(w) - d,
                "device": d,
            })
        return out

    def mean_layer(self, name: str) -> Optional[float]:
        device_read = name in ("device", "dispatch_host")
        if not self.traced or not self.requests or (device_read and not self.on_device):
            return None
        return sum(x[name] for x in self.layers) / len(self.layers)

    def min_time(self, call: Call) -> Optional[float]:
        """The least time the chip could take for the call's logical work."""
        if call.cost is None or self.peaks is None:
            return None
        ops, nbytes = call.cost
        return max(ops / self.peaks["ops_per_s"], nbytes / self.peaks["hbm_bytes_per_s"])
