"""The comparison that decides ``correct``: every answer the window
completed against the plain reference's answer to the same request.

An answer is a multiset of rows of terms; IRIs and literals compare as
text and numbers by value, exactly. A query with ``distinct`` takes the
reference's rows once each. A query with ``order`` (columns, each ``asc``
or ``desc``) and ``limit`` has to return a prefix of the reference's rows
in that order: as many rows as the limit leaves, their sort keys in the
order the reference's sorted rows give them, and each row one of the
reference's, so that rows tied at the cut may be any of the tied ones.
The numbers compared, each against a limit of its mix: ``errors``
(requests that raised) and ``wrong_answers`` (requests whose answer
differs).
"""

from __future__ import annotations

import collections
import pathlib
from typing import Dict, List, Sequence, Tuple

Row = Tuple

CONTROLS = pathlib.Path(__file__).resolve().parents[1] / "controls"


def canonical(value):
    if value is None:
        return None
    if isinstance(value, str):
        return ("s", value)
    return ("n", float(value))


def decode(dictionary, rows) -> List[Row]:
    """Rows of dictionary codes as rows of terms (None where unbound)."""
    if rows is None or len(rows) == 0:
        return []
    flat = dictionary.decode_many(int(c) for c in rows.reshape(-1))
    k = rows.shape[1]
    return [tuple(flat[i:i + k]) for i in range(0, len(flat), k)]


def _sort_key(value):
    return (0,) if value is None else (1, value)  # unbound sorts first, as in SPARQL


def _ordered(rows: List[Row], order) -> List[Row]:
    for col, direction in reversed(order):
        rows = sorted(rows, key=lambda r: _sort_key(r[col]), reverse=direction == "desc")
    return rows


def solution(rows: Sequence[Row], spec: dict) -> List[Row]:
    """The answer a query engine gives from the reference's rows: each
    row once under ``distinct``, sorted by ``order``, cut at ``limit``."""
    rows = list(rows)
    if spec.get("distinct"):
        rows = list(dict.fromkeys(rows))
    if "order" in spec:
        rows = _ordered(rows, spec["order"])
    return rows[:spec.get("limit", len(rows))]


def compare(got: Sequence[Row], want: Sequence[Row], spec: dict) -> bool:
    got = [tuple(canonical(v) for v in r) for r in got]
    want = [tuple(canonical(v) for v in r) for r in want]
    if spec.get("distinct"):
        want = list(dict.fromkeys(want))
    if "order" not in spec:
        return collections.Counter(got) == collections.Counter(want)
    order = spec["order"]
    expect = _ordered(want, order)[:spec.get("limit", len(want))]
    keys = lambda rows: [tuple(r[c] for c, _ in order) for r in rows]  # noqa: E731
    if len(got) != len(expect) or keys(got) != keys(expect):
        return False
    have = collections.Counter(want)
    return all(have[r] >= n for r, n in collections.Counter(got).items())


def control_answers(control: dict, reference, queries: Dict[str, dict],
                    requests) -> List[Tuple[str, List[Row]]]:
    """The control's answers to ``requests``: the plain reference put in
    the program's place with one guarantee of the configuration broken.
    The mix's ``control`` names its ``kind``, found by name as
    ``bench/controls/<kind>.py``, whose ``answers(reference, queries,
    requests, **params)`` gives them; the control's other keys are its
    params."""
    from bench.harness.runner import load_module  # runner imports this module

    params = dict(control)
    path = CONTROLS / f"{params.pop('kind')}.py"
    if not path.is_file():
        raise ValueError(f"unknown control {control['kind']!r}")
    return load_module(path).answers(reference, queries, requests, **params)


def judge(answers: Sequence[Tuple[str, Sequence[Row]]], reference, queries: Dict[str, dict],
          binds: Sequence[Dict[str, int]], n_errors: int, limits: Dict[str, float]):
    """The numbers compared, each with its limit, and whether all hold.
    ``answers`` are (query, rows) of the checked requests, ``binds`` their
    constants; ``reference.answer(query, bind)`` gives the expected rows."""
    cache: Dict[str, List[Row]] = {}
    wrong = 0
    for (query, rows), bind in zip(answers, binds):
        key = f"{query}|{sorted(bind.items())}"
        if key not in cache:
            cache[key] = reference.answer(query, bind)
        wrong += not compare(rows, cache[key], queries[query])
    values = {"errors": n_errors, "wrong_answers": wrong}
    numbers = {name: {"value": values[name], "limit": limit}
               for name, limit in limits.items()}
    correct = bool(answers) and all(n["value"] <= n["limit"] for n in numbers.values())
    return correct, numbers
