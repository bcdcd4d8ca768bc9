"""The comparison that decides ``correct``, on hand-made answers: exact
rows as multisets, DISTINCT, ORDER BY with LIMIT (any of the rows tied at
the cut, never one out of order), and the numbers compared against their
limits."""

import pytest

from bench.harness import check


def test_rows_compare_as_multisets_and_numbers_by_value():
    want = [("a", 1), ("b", 2.0), ("b", 2.0)]
    assert check.compare([("b", 2), ("a", 1.0), ("b", 2)], want, {})
    assert not check.compare([("b", 2), ("a", 1.0)], want, {})
    assert not check.compare([("a", 1), ("b", 2), ("b", 3)], want, {})
    assert not check.compare([("a", 1), ("b", 2), ("c", 2)], want, {})


def test_unbound_and_text_are_exact():
    assert check.compare([(None, "x")], [(None, "x")], {})
    assert not check.compare([("x", None)], [(None, "x")], {})


def test_distinct_takes_the_reference_rows_once():
    want = [("o1", 5.0), ("o1", 5.0), ("o2", 7.0)]
    assert check.compare([("o2", 7.0), ("o1", 5.0)], want, {"distinct": True})
    assert not check.compare(want, want, {"distinct": True})


ORDERED = {"order": [[1, "desc"]], "limit": 2}


@pytest.mark.parametrize("got,ok", [
    ([("c", 9), ("a", 5)], True),
    ([("c", 9), ("b", 5)], True),    # either row tied at the cut
    ([("a", 5), ("c", 9)], False),   # out of order
    ([("c", 9)], False),             # fewer than the limit leaves
    ([("c", 9), ("a", 4)], False),   # not the top rows
    ([("c", 9), ("x", 5)], False),   # a row the reference lacks
    ([("c", 9), ("c", 9)], False),   # one reference row twice
])
def test_order_by_with_limit_takes_an_ordered_prefix(got, ok):
    want = [("a", 5), ("c", 9), ("b", 5), ("d", 4)]
    assert check.compare(got, want, ORDERED) is ok


def test_solution_is_what_an_engine_answers():
    spec = {"order": [[1, "desc"]], "limit": 2, "distinct": True}
    want = [("a", 5), ("c", 9), ("c", 9), ("d", 4)]
    assert check.solution(want, spec) == [("c", 9), ("a", 5)]
    assert check.compare(check.solution(want, spec), want, spec)
    assert check.solution(want, {}) == want


def test_order_puts_unbound_first_and_a_short_answer_is_taken_whole():
    spec = {"order": [[0, "asc"]], "limit": 10}
    assert check.compare([(None,), (1,), (2,)], [(2,), (None,), (1,)], spec)
    assert not check.compare([(1,), (None,), (2,)], [(2,), (None,), (1,)], spec)
    assert check.compare([], [], spec)


class Ref:
    def __init__(self, answers):
        self.answers, self.calls = answers, 0

    def answer(self, query, bind):
        self.calls += 1
        return self.answers[(query, bind.get("x"))]


def test_judge_counts_wrong_answers_and_holds_each_number_to_its_limit():
    ref = Ref({("q", 1): [(1,)], ("q", 2): [(2,)], ("r", None): [("a", 1.0)]})
    queries = {"q": {}, "r": {}}
    answers = [("q", [(1,)]), ("q", [(3,)]), ("q", [(1,)]), ("r", [("a", 1.0)])]
    binds = [{"x": 1}, {"x": 2}, {"x": 1}, {}]
    limits = {"errors": 0, "wrong_answers": 0}
    correct, numbers = check.judge(answers, ref, queries, binds, 0, limits)
    assert not correct and numbers["wrong_answers"] == {"value": 1, "limit": 0}
    assert ref.calls == 3  # one reference answer per distinct request
    correct, numbers = check.judge(answers[:1] + answers[2:], Ref(ref.answers), queries,
                                   binds[:1] + binds[2:], 0, limits)
    assert correct
    correct, numbers = check.judge(answers[:1], Ref(ref.answers), queries, binds[:1], 1,
                                   limits)
    assert not correct and numbers["errors"] == {"value": 1, "limit": 0}


def test_nothing_answered_is_not_correct():
    assert check.judge([], Ref({}), {}, [], 0, {"errors": 0})[0] is False


def test_stale_control_answers_each_request_with_the_one_before_it():
    class Req:
        def __init__(self, query, x):
            self.query, self.bind = query, {"x": x}

    ref = Ref({("q", 1): [(1,)], ("q", 2): [(2,)], ("q", 3): [(3,)], ("r", 9): [(9,)]})
    reqs = [Req("q", 1), Req("r", 9), Req("q", 2), Req("q", 3)]
    queries = {"q": {}, "r": {}}
    got = check.control_answers({"kind": "stale_answers"}, ref, queries, reqs)
    assert got == [("q", [(3,)]), ("r", [(9,)]), ("q", [(1,)]), ("q", [(2,)])]
    with pytest.raises(ValueError):
        check.control_answers({"kind": "agg_dtype"}, ref, queries, reqs)
