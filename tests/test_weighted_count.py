"""Weighted counting (DESIGN.md §17): COUNT(*) plans that carry row
multiplicities through joins and fold away the variables nothing above
reads. Their counts equal the unweighted plan's (the planner's test-only
``weighted`` hook), the legacy row engine's and the mixed engine's on
seeded random graphs; a fold is a grouping with no aggregate, which
partitions and spills under a memory budget; a count above 2^31 is
exact; queries outside the rule plan as before, BSBM explore's plans
byte for byte; and LSQB's nine counts on the tiny cut of ``lsqb-sf0.1``
equal the benchmark's plain reference."""

import json
import pathlib

import numpy as np
import pytest

from repro.core import Engine, EngineConfig, QuadStore
from repro.core import planner as PL
from repro.core.batch import WEIGHT_VAR, row_weights, weight_pair
from repro.core.operators.aggregate import SortGroupBy, StreamingGroupBy
from repro.core.operators.sort import MaterializedSource

REPO = pathlib.Path(__file__).resolve().parents[1]

QUERIES = {
    "inner": "SELECT (COUNT(*) AS ?n) { ?a :knows ?b . ?b :knows ?c . ?c :likes ?t }",
    "cycle": "SELECT (COUNT(*) AS ?n) { ?a :knows ?b . ?b :knows ?c . ?c :knows ?a }",
    "optional": "SELECT (COUNT(*) AS ?n) { ?a :knows ?b . OPTIONAL { ?b :likes ?t } }",
    "optional_unmatched": (
        "SELECT (COUNT(*) AS ?n) { ?a :knows ?b . OPTIONAL { ?b :owns ?t } }"),
    "optional_filter": (
        "SELECT (COUNT(*) AS ?n) { ?a :knows ?b . "
        "OPTIONAL { ?b :likes ?t . ?a :likes ?u FILTER (?t != ?u) } }"),
    "minus": (
        "SELECT (COUNT(*) AS ?n) { ?a :knows ?b . ?b :knows ?c . ?c :likes ?t "
        "MINUS { ?a :knows ?c } }"),
    "not_exists": (
        "SELECT (COUNT(*) AS ?n) { ?a :likes ?t . ?a :knows ?b . ?b :likes ?u "
        "FILTER NOT EXISTS { ?b :likes ?t } }"),
    "filter_on_folded": (
        "SELECT (COUNT(*) AS ?n) { ?a :knows ?b . ?b :knows ?c . ?c :likes ?t "
        "FILTER (?a != ?c) }"),
    "union": (
        "SELECT (COUNT(*) AS ?n) { ?a :knows ?b . "
        "{ ?b :likes ?t } UNION { ?b :knows ?c . ?c :likes ?t } }"),
    "group_by": (
        "SELECT ?t (COUNT(*) AS ?n) { ?a :knows ?b . ?b :knows ?c . ?c :likes ?t } "
        "GROUP BY ?t"),
    "group_by_having": (
        "SELECT ?a (COUNT(*) AS ?n) { ?a :knows ?b . ?b :likes ?t } "
        "GROUP BY ?a HAVING (COUNT(*) > 3)"),
    "empty": "SELECT (COUNT(*) AS ?n) { ?a :knows ?b . ?b :owns ?t }",
}

# the rule applies, but a join or a condition reads every variable: nothing
# folds, and the plan is the unweighted one
READ_WHOLE = {"optional_filter"}

# the rule does not apply: the plan has no fold and is the unweighted one
UNCHANGED = {
    "select_distinct": (
        "SELECT DISTINCT (COUNT(*) AS ?n) { ?a :knows ?b . ?b :likes ?t }"),
    "count_var": "SELECT (COUNT(?t) AS ?n) { ?a :knows ?b . ?b :likes ?t }",
    "count_distinct": "SELECT (COUNT(DISTINCT ?t) AS ?n) { ?a :knows ?b . ?b :likes ?t }",
    "sum": "SELECT (SUM(?x) AS ?n) { ?a :knows ?b . ?b :age ?x }",
    "no_aggregate": "SELECT ?a ?t { ?a :knows ?b . ?b :likes ?t }",
}


def graph(seed: int, people: int = 30, tags: int = 8) -> QuadStore:
    rng = np.random.default_rng(seed)
    store = QuadStore()
    for i in range(people):
        for j in rng.choice(people, size=int(rng.integers(1, 7)), replace=False):
            if i != int(j):
                store.add(f":p{i}", ":knows", f":p{int(j)}")
        for t in rng.choice(tags, size=int(rng.integers(0, 4)), replace=False):
            store.add(f":p{i}", ":likes", f":t{int(t)}")
        store.add(f":p{i}", ":age", int(rng.integers(20, 60)))
    return store.build()


def answer(store, text, engine="barq", weighted=True):
    e = Engine(store, EngineConfig(engine=engine))
    if not weighted:
        e.planner.weighted = False
    res = e.execute(text)
    return sorted(tuple(store.dict.decode(int(c)) for c in row) for row in res.rows), res


def operators(op):
    yield op
    for child in getattr(op, "children", list)():
        yield from operators(child)


def folds(text, store, weighted=True) -> int:
    e = Engine(store)
    if not weighted:
        e.planner.weighted = False
    return e.explain(text).count(" fold(")


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", sorted(QUERIES))
def test_weighted_count_equals_unweighted_and_legacy(name, seed):
    store = graph(seed)
    text = QUERIES[name]
    got, res = answer(store, text)
    assert got == answer(store, text, weighted=False)[0]
    assert got == answer(store, text, engine="legacy")[0]
    assert got == answer(store, text, engine="mixed")[0]
    assert folds(text, store, weighted=False) == 0
    if name not in READ_WHOLE:
        assert folds(text, store) > 0 and "Fold" in res.profile()


@pytest.mark.parametrize("name", sorted(UNCHANGED))
def test_queries_outside_the_rule_plan_unweighted(name):
    store = graph(4)
    text = UNCHANGED[name]
    assert folds(text, store) == 0
    e, u = Engine(store), Engine(store)
    u.planner.weighted = False
    assert e.explain(text) == u.explain(text)
    assert answer(store, text)[0] == answer(store, text, engine="legacy")[0]


def test_a_count_above_2_to_the_31_is_exact():
    """A 3-arm star of 1,300 rows an arm: 1,300^3 solutions, counted from
    3,900 rows; the unweighted plan would emit 2.2e9."""
    store = QuadStore()
    for arm in (":a", ":b", ":c"):
        for i in range(1300):
            store.add(":hub", arm, f":x{i}")
    store.build()
    got, res = answer(store, "SELECT (COUNT(*) AS ?n) { ?h :a ?x . ?h :b ?y . ?h :c ?z }")
    assert got == [(1300 ** 3,)] and 1300 ** 3 > 2 ** 31
    assert res.trace.join_rows <= 2
    got, _ = answer(store, "SELECT ?h (COUNT(*) AS ?n) { ?h :a ?x . ?h :b ?y . ?h :c ?z } "
                           "GROUP BY ?h")
    assert got == [(":hub", 1300 ** 3)]


@pytest.mark.parametrize("w", [1, 2 ** 31 - 1, 2 ** 31, 2 ** 40 + 7, 2 ** 61 + 12345])
def test_weight_pair_round_trips_int64(w):
    lo, hi = weight_pair(np.asarray([w], dtype=np.int64))
    assert lo.dtype == np.int32 and hi.dtype == np.int32
    cols = np.stack([lo, hi])
    assert int(row_weights((WEIGHT_VAR, WEIGHT_VAR + 1), cols)[0]) == w


@pytest.mark.parametrize("sort_based", [False, True])
def test_fold_sums_weights_exactly_and_treats_unbound_pairs_as_one(sort_based):
    """Rows (k, pair a, pair b): a row weighs the product of its pairs; a
    pair left unbound (-1, an unmatched OPTIONAL) weighs 1. The fold is a
    grouping with no aggregate, streaming or sort-based."""
    big = 2 ** 40 + 3
    lo, hi = weight_pair(np.asarray([big, big, 5], dtype=np.int64))
    cols = np.asarray([[7, 7, 9],
                       lo, hi,
                       [3, -1, -1], [0, -1, -1]], dtype=np.int32)
    src = MaterializedSource((1, WEIGHT_VAR, WEIGHT_VAR + 1, WEIGHT_VAR + 2, WEIGHT_VAR + 3),
                             cols, sorted_var=1)
    pair = (WEIGHT_VAR + 4, WEIGHT_VAR + 5)
    fold = (SortGroupBy(src, (1,), (), None, weight=pair) if sort_based
            else StreamingGroupBy(src, 1, (), None, weight=pair))
    out = fold.next_batch()
    got = row_weights(out.var_ids, out.columns[:, : out.n_rows])
    assert out.var_ids == (1,) + pair and fold.sorted_by() == 1
    assert out.columns[0, : out.n_rows].tolist() == [7, 9]
    assert got.tolist() == [3 * big + big, 5]
    assert fold.stats.name == "Fold"
    assert fold.stats.extra["fold_rows_in"] == 3 and fold.stats.extra["fold_rows_out"] == 2


def test_a_fold_of_nothing_is_no_row_and_a_count_of_nothing_is_zero():
    src = MaterializedSource((1, WEIGHT_VAR, WEIGHT_VAR + 1), np.zeros((3, 0), dtype=np.int32))
    fold = StreamingGroupBy(src, None, (), None, weight=(WEIGHT_VAR + 2, WEIGHT_VAR + 3))
    assert fold.next_batch() is None


def test_join_rows_and_weights_are_counted_on_the_trace():
    store = graph(5)
    text = QUERIES["inner"]
    (row,), res = answer(store, text)
    tr = res.trace
    assert 0 < tr.join_rows < tr.join_weight
    _, plain = answer(store, text, weighted=False)
    assert plain.trace.join_rows == plain.trace.join_weight > tr.join_rows
    assert tr.summary()["join_rows"] == tr.join_rows
    counters = [e for e in tr.chrome_events() if e["ph"] == "C" and e["name"] == "join"]
    assert counters[0]["args"] == {"join_rows": tr.join_rows, "join_weight": tr.join_weight}
    ops = [e["name"] for e in tr.chrome_events() if e.get("cat") == "operator"]
    assert any(name.startswith("Fold") for name in ops)


def test_a_fold_keeps_the_order_a_merge_join_reads():
    store = graph(6)
    e = Engine(store, EngineConfig(join_strategy="merge"))
    text = QUERIES["inner"]
    plan = e.plan(e.parse(text)[0])

    def walk(n):
        if isinstance(n, PL.PMergeJoin):
            assert PL.phys_sorted_by(n.left) == n.var == PL.phys_sorted_by(n.right)
        for f in ("child", "left", "right", "probe", "build"):
            c = getattr(n, f, None)
            if isinstance(c, PL.PhysNode):
                walk(c)

    walk(plan)
    got = sorted(tuple(r) for r in e.execute(text).rows)
    assert [tuple(store.dict.decode(int(c)) for c in r) for r in got] == answer(
        store, text, engine="legacy")[0]


def test_bsbm_explore_plans_are_byte_identical():
    """The EXPLAIN of a BSBM explore mix on the benchmark's tiny store, as
    the engine before weighted counting planned it."""
    from bench.harness.runner import Cell
    from bench.harness.traffic import Traffic

    golden = json.loads((REPO / "tests" / "fixtures" / "bsbm_explore_explain.json").read_text())
    cell = Cell(REPO, "bsbm-25m.explore")
    tiny = json.loads((REPO / "bench" / "tests" / "tiny" / "bsbm-25m.json").read_text())
    ds = cell.generator.generate(dict(cell.config["params"], **tiny), golden["seed"])
    e = Engine(ds.load())
    window = Traffic(cell.mix, cell.queries, ds, golden["seed"]).window()
    got = {r.key: e.explain(r.text) for r in (next(window) for _ in cell.mix["order"])}
    assert got == golden["explain"]


@pytest.fixture(scope="module")
def lsqb_tiny():
    from bench.harness.runner import Cell, load_module

    cell = Cell(REPO, "lsqb-sf0.1.counts")
    tiny = json.loads((REPO / "bench" / "tests" / "tiny" / "lsqb-sf0.1.json").read_text())
    ds = cell.generator.generate(dict(cell.config["params"], **tiny), 2 ** 31 + 11)
    return cell, ds.load(), load_module(cell.reference_path).Reference(ds)


@pytest.mark.parametrize("query", ["q1", "q2", "q3", "q4", "q5", "q6", "q7", "q8", "q9"])
def test_lsqb_tiny_cut_matches_the_plain_reference(lsqb_tiny, query):
    cell, store, reference = lsqb_tiny
    text = cell.queries[query]["text"]
    got, _ = answer(store, text)
    assert got == [tuple(reference.answer(query, {})[0])]
    assert got == answer(store, text, weighted=False)[0]
    assert folds(text, store) > 0


@pytest.mark.parametrize("name", ["inner", "minus", "group_by"])
def test_folds_keep_to_the_memory_budget(name, tmp_path):
    """Under a memory budget a fold over budget is partitioned grouping,
    spilling like any GROUP BY, with the same count and no file left."""
    store = graph(7, people=400, tags=30)
    text = QUERIES[name]
    e = Engine(store, EngineConfig(memory_budget=4_000, spill_dir=str(tmp_path)))
    plan = PL.explain(e.plan(e.parse(text)[0]))
    assert any("Group[partitioned" in line and " fold(" in line for line in plan.splitlines())
    res = e.execute(text)
    folds_spilled = [op.stats.extra.get("spill_bytes", 0) for op in operators(res.root)
                     if op.stats.name == "Fold"]
    assert sum(folds_spilled) > 0
    assert sorted(map(tuple, res.rows)) == sorted(map(tuple, Engine(store).execute(text).rows))
    assert not list(tmp_path.iterdir())
