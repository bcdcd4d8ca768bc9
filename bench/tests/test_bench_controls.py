"""Controls found by name (``bench/controls/<kind>.py``): ``stale_answers``
gives what the control always gave, ``approximate_numbers`` rounds every
number of the reference's answer to its significant figures and leaves
text and unbound values alone, and an unknown kind is refused."""

from typing import Dict

import pytest

from bench.harness import check


class Ref:
    def __init__(self, answers):
        self.answers = answers

    def answer(self, query, bind):
        return self.answers[(query, bind.get("x"))]


class Req:
    def __init__(self, query, x=None):
        self.query, self.bind = query, {} if x is None else {"x": x}


def stale_before_controls_were_found_by_name(reference, queries, requests):
    """``check.control_answers`` as it was when it knew one control."""
    last: Dict[str, int] = {}
    for i, r in enumerate(requests):
        last[r.query] = i
    out = []
    for i, r in enumerate(requests):
        j = last[r.query]
        last[r.query] = i
        rows = reference.answer(r.query, requests[j].bind)
        out.append((r.query, check.solution(rows, queries[r.query])))
    return out


def test_stale_answers_found_by_name_answers_as_before():
    ref = Ref({("q", k): [(k, f"t{k}"), (None, k + 0.5)] for k in range(6)}
              | {("r", k): [(k,)] * k for k in range(6)})
    queries = {"q": {"order": [[0, "desc"]], "limit": 1}, "r": {"distinct": True}}
    reqs = [Req(q, k) for k, q in zip([3, 1, 4, 1, 5, 2, 0, 5], "qrqqrrqr")]
    got = check.control_answers({"kind": "stale_answers"}, ref, queries, reqs)
    assert got == stale_before_controls_were_found_by_name(ref, queries, reqs)


@pytest.mark.parametrize("value,digits,want", [
    (1234, 2, 1200), (616327, 2, 620000), (616327, 3, 616000), (1974336, 2, 2000000),
    (12, 2, 12), (7, 2, 7), (0, 2, 0), (99, 2, 99), (-4567, 2, -4600),
    (3.14159, 2, 3.1), (0.0012345, 2, 0.0012),
])
def test_approximate_numbers_rounds_to_significant_figures(value, digits, want):
    ref = Ref({("c", None): [(value,)]})
    got = check.control_answers({"kind": "approximate_numbers", "digits": digits}, ref,
                                {"c": {}}, [Req("c")])
    assert got == [("c", [(want,)])]
    assert type(got[0][1][0][0]) is type(value)


def test_approximate_numbers_keeps_text_and_unbound_and_rounds_to_two_by_default():
    ref = Ref({("q", 1): [("<a>", None, 1234), ('"12345"', 5.0, None)]})
    got = check.control_answers({"kind": "approximate_numbers"}, ref, {"q": {}}, [Req("q", 1)])
    assert got == [("q", [("<a>", None, 1200), ('"12345"', 5.0, None)])]


def test_approximate_numbers_fails_a_mix_without_constants_that_stale_answers_passes():
    ref = Ref({("q6", None): [(516,)], ("q9", None): [(504,)]})
    queries, reqs = {"q6": {}, "q9": {}}, [Req("q6"), Req("q9"), Req("q6"), Req("q9")]
    binds, limits = [r.bind for r in reqs], {"errors": 0, "wrong_answers": 0}
    stale = check.control_answers({"kind": "stale_answers"}, ref, queries, reqs)
    assert check.judge(stale, ref, queries, binds, 0, limits)[0] is True
    approx = check.control_answers({"kind": "approximate_numbers"}, ref, queries, reqs)
    correct, numbers = check.judge(approx, ref, queries, binds, 0, limits)
    assert correct is False and numbers["wrong_answers"]["value"] == 4


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="agg_dtype"):
        check.control_answers({"kind": "agg_dtype"}, Ref({}), {}, [Req("q", 1)])
