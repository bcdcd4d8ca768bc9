"""The ``approximate_numbers`` control: the plain reference's answer to
each request, with every number rounded to ``digits`` significant
figures, as an engine that estimated or sampled a count or a sum instead
of computing it would answer. It breaks exact answers on any request
whose answer holds a number of more significant figures than ``digits``,
whether or not the mix binds constants. Text and unbound values pass
through unchanged."""

from __future__ import annotations

from bench.harness.check import solution


def approximate(value, digits: int):
    if value is None or isinstance(value, str):
        return value
    return type(value)(float(f"{value:.{digits}g}"))


def answers(reference, queries, requests, digits: int = 2):
    out = []
    for r in requests:
        rows = solution(reference.answer(r.query, r.bind), queries[r.query])
        out.append((r.query, [tuple(approximate(v, digits) for v in row) for row in rows]))
    return out
