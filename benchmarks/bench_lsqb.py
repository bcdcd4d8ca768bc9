"""Fig. 6a — LSQB (CPU-bound joins): BARQ vs legacy per query + total
throughput ratio. The paper reports 3.4x total throughput, with the big
joins (Q6/Q9) ~83% faster; the per-tuple interpretation gap between
jitted-batch and Python-row execution makes the ratio larger here
(DESIGN.md §2 maps JVM virtual calls -> Python dispatch)."""

from __future__ import annotations

import argparse

from benchmarks.common import Suite, time_query
from repro.data import LSQB_QUERIES, generate_social_graph


def run(scale: float = 0.05, runs: int = 3, profile: bool = False) -> str:
    store, meta = generate_social_graph(scale=scale)
    suite = Suite(
        f"LSQB (Fig 6a) scale={scale} triples={meta['n_triples']} "
        f"barq vs legacy, {runs} runs"
    )
    total_barq = total_legacy = 0.0
    for name, q in LSQB_QUERIES.items():
        b = time_query(store, q, "barq", runs=runs)
        l = time_query(store, q, "legacy", runs=runs)
        total_barq += b["mean_s"]
        total_legacy += l["mean_s"]
        suite.add(
            f"lsqb_{name}_barq", b["mean_s"] * 1e6,
            f"rows={b['rows']};speedup_vs_legacy={l['mean_s'] / max(b['mean_s'], 1e-9):.1f}x",
        )
        suite.add(f"lsqb_{name}_legacy", l["mean_s"] * 1e6, f"rows={l['rows']}")
    suite.add(
        "lsqb_total_barq", total_barq * 1e6,
        f"throughput_ratio={total_legacy / max(total_barq, 1e-9):.2f}x (paper: 3.4x)",
    )

    if profile:
        from repro.core import Engine, EngineConfig

        e = Engine(store, EngineConfig(engine="barq"))
        r = e.execute(LSQB_QUERIES["q6"])
        print(r.profile())
    return suite.emit()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--profile", action="store_true")
    a = ap.parse_args()
    print(run(a.scale, a.runs, a.profile))
