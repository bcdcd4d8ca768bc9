"""The ``stale_answers`` control: each request answered with the plain
reference's answer to the previous request of its query (the first with
the last's), as a result cache keyed on the query's template would. It
breaks exact answers only where a query's requests differ in their
constants: a mix without constants cannot fail it."""

from __future__ import annotations

from typing import Dict

from bench.harness.check import solution


def answers(reference, queries, requests):
    last: Dict[str, int] = {}
    for i, r in enumerate(requests):
        last[r.query] = i
    out = []
    for i, r in enumerate(requests):
        j = last[r.query]
        last[r.query] = i
        rows = reference.answer(r.query, requests[j].bind)
        out.append((r.query, solution(rows, queries[r.query])))
    return out
