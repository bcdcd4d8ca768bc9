"""Chip smoke: serve BSBM queries through BARQ on one TPU and check them.

    python chip_smoke.py [--seed 7]

Builds a BSBM-shaped store at the product count of the BSBM 25M-triple
dataset (70,812 products), then serves the explore templates e1-e5
(4 instances each, constants drawn from ``--seed``) and the BI queries
b1, b3, b5 and b8 through ``QueryServer.execute``, once on the numpy data
plane (the reference) and twice on the platform's own plane: a cold pass,
which compiles, and a warm pass. Every answer of both passes must equal
the reference: rows exactly, float aggregates to a relative 1e-5 (the
device path computes in float32).

Earlier lines report the store size, rows and latency per query, the
programs compiled (fresh and from the persistent cache, counted by the
kernel that caused them) and the kernel ledger by (kernel, backend). The last line is one JSON object naming the
device. The run fails, and prints no such line, when JAX finds no TPU,
when any kernel dispatch of the device passes ran on a backend other than
pallas, when the reference pass left the numpy plane, or when any answer
differs from the reference. Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import math
import os
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

from repro.core import telemetry  # noqa: E402
from repro.core.dictionary import Dictionary  # noqa: E402
from repro.core.storage import QuadStore  # noqa: E402
from repro.data import (  # noqa: E402
    BSBM_BI_QUERIES,
    BSBM_EXPLORE_TEMPLATES,
    generate_ecommerce_graph,
    instantiate_explore,
)
from repro.kernels import ops  # noqa: E402
from repro.serve.query_server import QueryServer  # noqa: E402

# BSBM 25M-triple dataset: 70,812 products; the generator makes 4000 per
# unit of scale
SCALE = 70812 / 4000
INSTANCES = 4
BI_QUERIES = ("b1", "b3", "b5", "b8")  # b6 builds ~1e8 rows: benchmark only
FLOAT_RTOL = 1e-5


def workload(store: QuadStore, meta: Dict[str, int], seed: int,
             instances: int) -> List[Tuple[str, str]]:
    """(key, query text) requests: each explore template with
    ``instances`` constants drawn from ``seed``, then the BI queries."""
    rng = np.random.RandomState(seed)
    reqs = []
    for name, tmpl in BSBM_EXPLORE_TEMPLATES.items():
        for i in range(instances):
            text = (bind_type_feature(tmpl, store, rng)
                    if "%FEATURE%" in tmpl else tmpl)
            reqs.append((f"{name}.{i}", instantiate_explore(text, meta, rng)))
    return reqs + [(name, BSBM_BI_QUERIES[name]) for name in BI_QUERIES]


def bind_type_feature(template: str, store: QuadStore, rng) -> str:
    """Bind %TYPE% and %FEATURE% to the type and a feature of one product,
    so that the answer holds at least that product. (The generator's own
    draw fixes :feature0, which almost no type's products have at this
    scale, and would leave the answer empty.)"""
    d = store.dict
    pairs = store.range_for_pattern(
        "psoc", (None, d.lookup(":productFeature"), None, None))
    _, product, feature, _ = store.read(pairs, rng.randint(len(pairs)), 1)[0]
    types = store.range_for_pattern(
        "spoc", (int(product), d.lookup("rdf:type"), None, None))
    type_ = store.read(types, 0, 1)[0][2]
    return (template.replace("%TYPE%", d.decode(int(type_)))
            .replace("%FEATURE%", d.decode(int(feature))))


class Pass:
    """One pass of the requests through a fresh QueryServer."""

    def __init__(self, store: QuadStore, requests: Sequence[Tuple[str, str]],
                 backend: Optional[str] = None):
        server = QueryServer(store)
        self.answers: Dict[str, list] = {}
        self.n_rows: Dict[str, int] = {}
        self.latency_s: Dict[str, float] = {}
        # backend None: each dispatch takes the platform's data plane
        ctx = ops.data_plane(backend) if backend else contextlib.nullcontext()
        with ctx:
            for key, text in requests:
                r = server.execute(key, text)
                self.answers[key] = canonical(r.rows, store.dict)
                self.n_rows[key] = r.n_rows
                self.latency_s[key] = r.latency_s
        self.ledger: telemetry.KernelLedger = server.metrics.kernels


def canonical(rows: np.ndarray, d: Dictionary) -> list:
    """Decoded rows in an order that does not depend on float rounding:
    sorted by their non-float terms, then by their floats."""
    decoded = [
        tuple(None if c < 0 else d.decode(int(c)) for c in row) for row in rows
    ]

    def key(row):
        exact = tuple(repr(v) for v in row if not isinstance(v, float))
        return exact, tuple(v for v in row if isinstance(v, float))

    return sorted(decoded, key=key)


def same_answer(got: list, want: list) -> bool:
    if len(got) != len(want):
        return False
    for g_row, w_row in zip(got, want):
        if len(g_row) != len(w_row):
            return False
        for g, w in zip(g_row, w_row):
            if type(g) is not type(w):
                return False
            if isinstance(g, float):
                if not math.isclose(g, w, rel_tol=FLOAT_RTOL, abs_tol=0.0):
                    return False
            elif g != w:
                return False
    return True


def mismatches(ref: Pass, run: Pass) -> List[str]:
    return [k for k in ref.answers if not same_answer(run.answers[k], ref.answers[k])]


def _report_compiles(label: str, before, after) -> None:
    programs = after[0] - before[0]
    hits = after[1] - before[1]
    by_kernel = collections.Counter(
        k for k, _, _, _ in telemetry.compile_ledger().events[before[0]:after[0]])
    print(f"compile pass={label} programs={programs} fresh={programs - hits} "
          f"cache_hits={hits} seconds={after[2] - before[2]} "
          f"by_kernel={dict(by_kernel)}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    from repro import compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "the smoke runs only on the chip", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device {json.dumps(device)}")
    print(f"compile_cache dir={compile_cache.enable()}")
    # fed by the kernels package from the first device dispatch on
    counter = telemetry.compile_ledger()

    t0 = time.perf_counter()
    store, meta = generate_ecommerce_graph(scale=SCALE, seed=args.seed)
    print(f"store products={meta['n_product']} offers={meta['n_offer']} "
          f"triples={meta['n_triples']} terms={len(store.dict)} "
          f"build_s={time.perf_counter() - t0}")
    requests = workload(store, meta, args.seed, INSTANCES)

    ref = Pass(store, requests, backend="numpy")
    c0 = counter.snapshot()
    cold = Pass(store, requests)
    c1 = counter.snapshot()
    warm = Pass(store, requests)
    c2 = counter.snapshot()

    bad = sorted(set(mismatches(ref, cold)) | set(mismatches(ref, warm)))
    for key, _ in requests:
        print(f"query {key} rows={cold.n_rows[key]} "
              f"ref_ms={ref.latency_s[key] * 1e3} "
              f"cold_ms={cold.latency_s[key] * 1e3} "
              f"warm_ms={warm.latency_s[key] * 1e3} "
              f"match={'no' if key in bad else 'yes'}")
    _report_compiles("cold", c0, c1)
    _report_compiles("warm", c1, c2)

    by_backend = collections.Counter()
    for pass_ in (cold, warm):
        for (name, backend), n in pass_.ledger.backend_counts.items():
            by_backend[(name, backend)] += n
    for (name, backend), n in sorted(by_backend.items()):
        wall = sum(p.ledger.backend_wall_s.get((name, backend), 0.0)
                   for p in (cold, warm))
        print(f"ledger kernel={name} backend={backend} calls={n} "
              f"wall_ms={wall * 1e3}")

    failures = []
    if bad:
        failures.append(f"answers differ from the numpy plane: {bad}")
    off_chip = sorted({b for (_, b) in by_backend if b != "pallas"})
    if off_chip:
        failures.append(f"dispatches on backends other than pallas: {off_chip}")
    if not by_backend:
        failures.append("no kernel dispatch reached the device")
    ref_planes = sorted({b for (_, b) in ref.ledger.backend_counts})
    if ref_planes not in ([], ["numpy"]):
        failures.append(f"reference dispatches off the numpy plane: {ref_planes}")
    for msg in failures:
        print(f"chip_smoke: FAIL {msg}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
