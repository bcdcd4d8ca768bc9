"""End-to-end behaviour tests for the paper's system: full query pipeline
(parse → optimize → translate → execute → decode) on the paper's own
workload shapes and engine co-existence."""

import numpy as np
import pytest

from repro.core import Engine, EngineConfig
from repro.core.profiler import collect_stats
from repro.data import (
    BSBM_BI_QUERIES,
    BSBM_EXPLORE_TEMPLATES,
    LSQB_QUERIES,
    generate_ecommerce_graph,
    generate_social_graph,
    instantiate_explore,
)


@pytest.fixture(scope="module")
def social():
    return generate_social_graph(scale=0.04, seed=1)


@pytest.fixture(scope="module")
def shop():
    return generate_ecommerce_graph(scale=0.05, seed=2)


def _count(store, q, engine):
    r = Engine(store, EngineConfig(engine=engine)).execute(q)
    return int(store.dict.decode(int(r.rows[0, 0])))


@pytest.mark.parametrize("qname", sorted(LSQB_QUERIES))
def test_lsqb_queries_all_engines_agree(social, qname):
    store, _ = social
    counts = {e: _count(store, LSQB_QUERIES[qname], e)
              for e in ("barq", "legacy", "mixed")}
    assert len(set(counts.values())) == 1, counts
    # CPU-bound suite should actually produce work
    if qname in ("q1", "q6", "q9"):
        assert counts["barq"] > 0


@pytest.mark.parametrize("tname", sorted(BSBM_EXPLORE_TEMPLATES))
def test_bsbm_explore_templates(shop, tname):
    store, meta = shop
    rng = np.random.RandomState(7)
    q = instantiate_explore(BSBM_EXPLORE_TEMPLATES[tname], meta, rng)
    rb = Engine(store, EngineConfig(engine="barq")).execute(q)
    rl = Engine(store, EngineConfig(engine="legacy")).execute(q)
    assert sorted(map(tuple, rb.rows.tolist())) == sorted(
        map(tuple, rl.rows.tolist())
    )


@pytest.mark.parametrize("qname", sorted(BSBM_BI_QUERIES))
def test_bsbm_bi_queries(shop, qname):
    store, _ = shop
    rb = Engine(store, EngineConfig(engine="barq")).execute(BSBM_BI_QUERIES[qname])
    rl = Engine(store, EngineConfig(engine="legacy")).execute(BSBM_BI_QUERIES[qname])
    decode = lambda r: sorted(  # noqa: E731
        tuple(None if c == -1 else store.dict.decode(int(c)) for c in row)
        for row in r.rows.tolist()
    )
    assert decode(rb) == decode(rl)


def test_profiler_reports_tree(social):
    store, _ = social
    r = Engine(store, EngineConfig(engine="barq")).execute(LSQB_QUERIES["q6"])
    prof = r.profile()
    # the cost-based planner may pick either join strategy here (§11)
    assert ("MergeJoin" in prof or "HashJoin" in prof)
    assert "Scan" in prof and "wall" in prof
    stats = collect_stats(r.root)
    assert stats["rows_scanned"] > 0 and stats["operators"] >= 5


def test_adaptive_batching_reduces_overfetch(shop):
    """§3.4: adaptive sizing must not scan more than a large fixed batch."""
    store, meta = shop
    rng = np.random.RandomState(0)
    q = instantiate_explore(BSBM_EXPLORE_TEMPLATES["e2"], meta, rng)

    def scanned(cfg):
        r = Engine(store, cfg).execute(q)
        return collect_stats(r.root)["rows_scanned"]

    adaptive = scanned(EngineConfig(engine="barq", adaptive_batching=True))
    fixed = scanned(
        EngineConfig(engine="barq", adaptive_batching=False,
                     initial_batch=4096, max_batch=4096)
    )
    assert adaptive <= fixed
