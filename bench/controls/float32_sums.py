"""The ``float32_sums`` control: the plain reference's answers with each
count summed in float32, one term after another, instead of in int64, as
a program that accumulated its counts or row weights in float32 (the
device's segmented scans, DESIGN.md §9.5) would answer. Sums stay exact
below 2^24 and drift above it, so it breaks a count past 2^24 and no
other. It needs a reference that takes ``acc``, the dtype it sums counts
in (``references/lsqb.py``)."""

from __future__ import annotations

import numpy as np

from bench.harness.check import solution


def answers(reference, queries, requests):
    low = type(reference)(reference.ds, acc=np.float32)
    return [(r.query, solution(low.answer(r.query, r.bind), queries[r.query]))
            for r in requests]
