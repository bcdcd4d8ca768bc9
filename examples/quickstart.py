"""Quickstart: load a graph, run SPARQL through BARQ, inspect the profile.

    PYTHONPATH=src python examples/quickstart.py
"""

from repro import compile_cache
from repro.core import Engine, EngineConfig, QuadStore

compile_cache.enable()

# 1. build a store (insertion API; bulk loading uses add_encoded)
store = QuadStore()
store.add(":Alice", ":knows", ":Bob")
store.add(":Alice", ":knows", ":Carol")
store.add(":Bob", ":knows", ":Carol")
store.add(":Carol", ":knows", ":Dave")
store.add(":Bob", ":worksAt", ":ACME")
store.add(":Carol", ":worksAt", ":ACME")
store.add(":Dave", ":worksAt", ":Initech")
store.add(":Alice", ":age", 31)
store.add(":Bob", ":age", 42)
store.add(":Alice", ":name", '"Alice Liddell"')
store.add(":Bob", ":name", '"Bob Cratchit"')
store.add(":Carol", ":name", '"Carol Danvers"')
store.add(":Dave", ":name", '"Dave Bowman"')
store.build()

# 2. the motivating-example query shape (Figure 1 of the paper)
QUERY = """
SELECT ?a ?c ?company {
  ?a :knows ?b .
  ?b :knows ?c .
  ?c :worksAt ?company .
  FILTER (?a != ?c)
}
"""

engine = Engine(store, EngineConfig(engine="barq"))
result = engine.execute(QUERY)
print("rows:")
for row in result.decoded(store.dict):
    print("  ", row)

# 3. operator-tree profile (paper Listing 1 style)
print("\nprofile:")
print(result.profile())

# 4. same query on the legacy row-based engine — identical answers
legacy = Engine(store, EngineConfig(engine="legacy")).execute(QUERY)
assert sorted(map(str, legacy.decoded(store.dict))) == sorted(
    map(str, result.decoded(store.dict))
)
print("\nlegacy engine agrees ✓")

# 5. aggregation + numeric filter
AGG = """
SELECT ?p (COUNT(DISTINCT ?q) AS ?n) {
  ?p :knows ?q .
} GROUP BY ?p
"""
print("\nfriend counts:", Engine(store).execute(AGG).decoded(store.dict))

# 5b. the vectorized grouping engine (DESIGN.md §10): multi-key GROUP BY
# runs through packed composite keys + segmented-reduction kernels, and
# HAVING filters the aggregate output through the expression VM. Aggregate
# calls are legal inside HAVING — COUNT(?p) here desugars to a hidden
# aggregate the projection strips.
HAVING_Q = """
SELECT ?company (AVG(?age) AS ?avgage) {
  ?p :worksAt ?company .
  OPTIONAL { ?p :age ?age }
} GROUP BY ?company HAVING (COUNT(?p) >= 2)
"""
having_result = Engine(store).execute(HAVING_Q)
print("\ncompanies with >= 2 people (avg age; unbound if none known):")
for row in having_result.decoded(store.dict):
    print("  ", row)
# the profile shows the Group operator's kernel counters
# (group_runs / segment_reduce / segment_reduce_ms) and the Having stage
print("\ngrouping profile:")
print(having_result.profile())

# 6. property paths: the vectorized frontier engine (DESIGN.md §8).
# `:knows+` is the transitive closure; `/` sequences into :worksAt.
PATH = """
SELECT ?reach ?company {
  :Alice :knows+/:worksAt? ?reach .
  ?reach :worksAt ?company
}
"""
path_result = engine.execute(PATH)
print("\nAlice's transitive network (with employers):")
for row in path_result.decoded(store.dict):
    print("  ", row)
# the profile shows the PathExpand operator with its frontier metrics
# (rounds, peak frontier, dedup ratio) and the seed-side choice
print("\npath profile:")
print(path_result.profile())

# 6b. join strategies (DESIGN.md §11): EXPLAIN-style plan output. The
# UNION's output arrives unsorted on ?b, so sorting both inputs for a
# merge join would cost two O(n log n) pipeline breakers — the cost model
# picks the radix-partitioned HashJoin instead (probe side streams
# unsorted; the build side is partitioned once). Forcing join_strategy
# shows the alternative plan; FILTER NOT EXISTS plans onto the same
# machinery as an anti hash/merge join.
from repro.core.planner import explain

STRAT = """
SELECT ?a ?b ?company {
  { ?a :knows ?b } UNION { ?b :knows ?a }
  OPTIONAL { ?b :worksAt ?company }
  FILTER NOT EXISTS { ?b :worksAt :Initech }
}
"""
node, vt = engine.parse(STRAT)
print("\nchosen plan (cost-based — note HashJoin, no Sort below it):")
print(explain(engine.plan(node), vt))
forced = Engine(store, EngineConfig(join_strategy="merge"))
print("\nforced join_strategy='merge' (the pre-§11 double-Sort shape):")
print(explain(forced.plan(forced.parse(STRAT)[0]), vt))
strat_rows = engine.execute(STRAT).decoded(store.dict)
assert sorted(map(str, forced.execute(STRAT).decoded(store.dict))) == sorted(
    map(str, strat_rows)
)
print("\nboth strategies agree ✓:", strat_rows)

# 7. the expression VM (DESIGN.md §9): FILTER/BIND compile to bytecode
# programs at plan time — string predicates evaluate once per distinct
# dictionary term, three-valued logic is exact (COALESCE recovers the
# rows where ?age is unbound instead of erroring them away).
EXPR = """
SELECT ?p ?name ?grp {
  ?p :name ?name .
  OPTIONAL { ?p :age ?age }
  FILTER(REGEX(?name, "^[A-C]") && !CONTAINS(?name, "z"))
  BIND(IF(COALESCE(?age, 0) >= 40, 1, 0) AS ?grp)
}
"""
expr_result = engine.execute(EXPR)
print("\nexpression VM (FILTER(REGEX) + BIND(IF/COALESCE)):")
for row in expr_result.decoded(store.dict):
    print("  ", row)
# the profile's Filter[vm] line carries the program size and fused
# dispatch count/time: expr_ops / expr_dispatches / expr_eval_ms
print("\nexpression profile:")
print(expr_result.profile())

# 8. sideways information passing (DESIGN.md §12): when a join's build
# side is much smaller than its probe side, the planner annotates
# probe-side scans with SipFilter prefilters — the build phase exports a
# bloom filter + key code range, and the scans seek past rows that
# cannot survive the join before the join ever sees them. explain()
# shows the pushed filters (sip=[...] on scans) and their exporters
# (sip-export=[...] on joins); sip="off" disables the rewrite.
SIP_Q = """
SELECT ?p ?q ?company {
  ?p :knows ?q .
  ?p :worksAt ?company .
  ?p :age ?age .
}
"""
sip_engine = Engine(store, EngineConfig(sip="on"))
node, vt = sip_engine.parse(SIP_Q)
print("\nplan with sideways information passing (note sip=/sip-export=):")
print(explain(sip_engine.plan(node), vt))
sip_rows = sip_engine.execute(SIP_Q).decoded(store.dict)
off_rows = Engine(store, EngineConfig(sip="off")).execute(SIP_Q).decoded(store.dict)
assert sorted(map(str, sip_rows)) == sorted(map(str, off_rows))
# the profile surfaces what SIP did: sip_range_seeks / sip_pruned_rows
# on scans, sip_exports on the joins that produced the filters
print("\nSIP on/off agree ✓:", sip_rows)

# 9. query telemetry (DESIGN.md §13): every execute records a QueryTrace —
# lifecycle spans, a per-query kernel ledger (dispatch counts + wall time
# by kernel and backend, exact even when a server interleaves queries),
# and EXPLAIN ANALYZE: the planner's cardinality estimates printed next
# to actual rows, with MISEST(q=...) flags at q-error >= 4.
result2 = engine.execute(QUERY)
print("\nEXPLAIN ANALYZE (est vs actual, misestimates flagged):")
print(result2.explain_analyze())
trace = result2.trace
print("\nlifecycle spans (ms):",
      {name: round(dur * 1e3, 2) for name, _c, _t, dur, _a in trace.spans})
print("kernel ledger:", dict(trace.ledger.counts))
print("pool delta (this query only):", result2.pool_delta())
# the trace exports Chrome-trace JSON — open in ui.perfetto.dev
trace.save_chrome_trace("/tmp/quickstart.trace.json")
print("wrote /tmp/quickstart.trace.json (Perfetto-loadable)")

# 9b. serving metrics: QueryServer aggregates per-request telemetry into
# a registry with sliding-window p50/p99/QPS, plan-cache hit rates, and
# kernel/pool attribution — exported as JSON for dashboards.
from repro.serve.query_server import QueryServer

server = QueryServer(store, EngineConfig(engine="barq"))
workload = [("fig1", QUERY), ("agg", AGG)] * 3
print("\nserved workload:", server.run_workload(workload, warmup=2))
print("metrics snapshot:")
print(server.metrics_json())

# 10. workload history + cardinality feedback (DESIGN.md §14): queries
# group under a canonical template fingerprint (literals, whitespace and
# variable names normalized away), and the engine records each plan
# node's *actual* cardinality into a feedback store keyed by stable node
# fingerprints. Under cardinality_feedback="apply" the planner reads
# those observations back: a query that misestimates on its first run
# (MISEST flags in EXPLAIN ANALYZE) re-plans from observed cardinalities
# on its second — estimates print as est=...(source=feedback) and the
# MISEST flags disappear.
FEEDBACK_Q = """
SELECT ?a ?c {
  ?a :knows ?b . ?b :knows ?c . ?c :age ?x .
  FILTER(?x > 25)
}
"""
# a store big enough that misestimates are real correlation effects, not
# tiny-count noise: a cyclic :knows graph defeats the independence
# assumption on the two-hop join
fb_store = QuadStore()
for i in range(120):
    fb_store.add(f":p{i}", ":knows", f":p{(i * 7 + 1) % 120}")
    fb_store.add(f":p{i}", ":age", 20 + i % 30)
fb_store = fb_store.build()
fb_engine = Engine(fb_store, EngineConfig(engine="barq",
                                          cardinality_feedback="apply"))
run1 = fb_engine.execute(FEEDBACK_Q)
print("\nrun 1 (cold estimates — note any MISEST flags):")
print(run1.explain_analyze())
run2 = fb_engine.execute(FEEDBACK_Q)
print("\nrun 2 (re-planned from observed cardinalities):")
print(run2.explain_analyze())
assert "MISEST" not in run2.explain_analyze()
assert run1.n_rows == run2.n_rows  # feedback changes plans, not answers

# the serving layer accumulates the same history per fingerprint: top
# templates by wall time, q-error leaderboard, latency regressions, and
# an OpenMetrics exposition for scrape-based monitoring
from repro.serve.metrics import validate_openmetrics

fb_server = QueryServer(fb_store, EngineConfig(
    engine="barq", cardinality_feedback="apply"))
fb_server.execute("fq", FEEDBACK_Q)
fb_server.execute("fq", FEEDBACK_Q)
top = fb_server.workload.top_by_wall(3)
print("\nworkload history (top templates):",
      [(t["fingerprint"][:8], t["n"], t["max_q_error"]) for t in top])
exposition = fb_server.openmetrics()
validate_openmetrics(exposition)
print("OpenMetrics exposition validates ✓ "
      f"({exposition.count(chr(10))} lines)")

# 11. out-of-core execution (DESIGN.md §15): EngineConfig.memory_budget
# caps the bytes a pipeline breaker may keep resident. A hash join whose
# build side exceeds it becomes a *grace* hash join — both inputs are
# radix-partitioned once (same key, same partition), non-resident
# partitions spill to spill_dir, and the join is built one partition at
# a time; skewed partitions re-partition recursively with a different
# hash. EXPLAIN shows the chosen fan-out and expected spill up front,
# and the spill counters land in EXPLAIN ANALYZE and the serving
# metrics. With memory_budget=None (the default) plans are untouched.
import tempfile

import numpy as np

rng = np.random.RandomState(11)
big = QuadStore()
for i in range(30_000):
    big.add(f":u{i:06d}", ":follows", f":u{rng.randint(0, 30_000):06d}")
    big.add(f":u{i:06d}", ":city", f":c{rng.randint(0, 200):03d}")
big = big.build()
GRACE_Q = "SELECT ?a ?b ?c { ?a :follows ?b . ?a :city ?c }"

spill_dir = tempfile.mkdtemp(prefix="barq-spill-")
tiny_budget = 64 * 1024  # far below the ~240KB build side
grace_engine = Engine(big, EngineConfig(
    engine="barq", join_strategy="hash",
    memory_budget=tiny_budget, spill_dir=spill_dir,
))
grace_res = grace_engine.execute(GRACE_Q)
print("\ngrace plan under a 64KB memory budget:")
print(grace_engine.explain(GRACE_Q))
print(grace_res.explain_analyze())

resident = Engine(big, EngineConfig(engine="barq", join_strategy="hash"))
assert grace_res.n_rows == resident.execute(GRACE_Q).n_rows
assert "grace" in grace_engine.explain(GRACE_Q)
print(f"same {grace_res.n_rows} rows as the resident build, "
      f"spill dir empty again: {not __import__('glob').glob(spill_dir + '/*.npy')}")

# 12. correctness tooling (DESIGN.md §16): three machine-checked layers.
# barqlint statically checks pool/kernel/stats/dtype discipline over the
# tree (`make lint`); EngineConfig.verify_plans re-derives the planner's
# structural invariants on every plan (sortedness under merge joins,
# SIP soundness, grace/adaptive gating) and raises naming the node;
# EngineConfig.sanitize swaps the arena for a SanitizingBatchPool that
# poisons released buffers and turns ownership-protocol violations into
# immediate SanitizeErrors attributed to the allocating operator. CI
# runs the whole suite with both knobs on (BARQ_SANITIZE=1
# BARQ_VERIFY_PLANS=1) — here we just show the pieces working.
from repro.analysis.lint import RULES, lint_paths
from repro.analysis.sanitize import SanitizeError

hardened = Engine(store, EngineConfig(
    engine="barq", sanitize=True, verify_plans=True))
hr = hardened.execute(QUERY)
c = hardened.pool.counters()
assert c["live"] == 0 and c["allocs"] == c["releases"] + c["pooled"]
assert hardened.pool.leaks() == []
print(f"\nhardened run: {hr.n_rows} rows, pool conservation {c}")

from repro.core.batch import ColumnBatch

victim = ColumnBatch.from_columns((0,), [np.arange(4, dtype=np.int32)],
                                  pool=hardened.pool)
victim.release()
try:
    victim.column(0)
except SanitizeError as e:
    print(f"use-after-release caught: {str(e)[:72]}...")

print(f"barqlint: {len(RULES)} rules, "
      f"{len(lint_paths([__import__('pathlib').Path('src')]))} findings on src/")
