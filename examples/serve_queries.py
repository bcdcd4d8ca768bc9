"""End-to-end driver: serve a batched SPARQL workload (the paper's kind of
system serves queries, not tokens).

Generates LSQB-like + BSBM-like stores, builds a mixed OLTP/analytical
request stream, and serves it through the BARQ engine with plan caching,
reporting throughput and latency percentiles for BARQ vs the legacy
executor (paper §5's comparison, as a serving loop).

    PYTHONPATH=src python examples/serve_queries.py [--requests 200]
"""

import argparse

import numpy as np

from repro import compile_cache
from repro.core import EngineConfig
from repro.data import (
    BSBM_EXPLORE_TEMPLATES,
    LSQB_QUERIES,
    generate_ecommerce_graph,
    generate_social_graph,
    instantiate_explore,
)
from repro.serve.query_server import QueryServer


def build_workload(meta, n_requests: int, seed: int = 0):
    """80% OLTP point lookups + 20% analytical (a realistic mix)."""
    rng = np.random.RandomState(seed)
    reqs = []
    explore = list(BSBM_EXPLORE_TEMPLATES.items())
    for i in range(n_requests):
        if rng.rand() < 0.8:
            key, tpl = explore[rng.randint(len(explore))]
            reqs.append((f"explore_{key}", instantiate_explore(tpl, meta, rng)))
        else:
            key = rng.choice(["q1", "q2", "q5"])
            reqs.append((f"lsqb_{key}", None))  # filled below
    return reqs


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--scale", type=float, default=0.15)
    args = ap.parse_args()

    print("generating stores...")
    social, smeta = generate_social_graph(scale=args.scale)
    shop, emeta = generate_ecommerce_graph(scale=args.scale)

    workload = build_workload(emeta, args.requests)

    for engine in ("barq", "legacy"):
        shop_server = QueryServer(shop, EngineConfig(engine=engine))
        social_server = QueryServer(social, EngineConfig(engine=engine))
        import time

        lats = []
        rows = 0
        t0 = time.perf_counter()
        for key, text in workload:
            if text is None:
                q = LSQB_QUERIES[key.split("_", 1)[1]]
                r = social_server.execute(key, q)
            else:
                r = shop_server.execute(key, text)
            lats.append(r.latency_s)
            rows += r.n_rows
        wall = time.perf_counter() - t0
        lats_ms = np.asarray(lats) * 1e3
        print(
            f"[{engine:6s}] {len(workload)} requests in {wall:.2f}s "
            f"({len(workload) / wall:.1f} qps) | rows={rows} | "
            f"p50={np.percentile(lats_ms, 50):.2f}ms "
            f"p95={np.percentile(lats_ms, 95):.2f}ms "
            f"p99={np.percentile(lats_ms, 99):.2f}ms"
        )


if __name__ == "__main__":
    main()
