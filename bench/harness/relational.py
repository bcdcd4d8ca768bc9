"""The plain relational steps the reference answers are written in:
triple patterns as sorted (key, value) indexes, joins as row expansion,
membership tests against a predicate's sorted pairs. numpy only; nothing
here comes from the system under test."""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from bench.harness.dataset import Dataset

Rows = Dict[str, np.ndarray]


class Index:
    """The (key, value) pairs of one predicate, sorted by key."""

    def __init__(self, keys: np.ndarray, values: np.ndarray):
        order = np.argsort(keys, kind="stable")
        self.keys = keys[order]
        self.values = values[order]

    def lookup(self, key: int) -> np.ndarray:
        lo, hi = np.searchsorted(self.keys, [key, key + 1])
        return self.values[lo:hi]

    def expand(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(row, value): every value paired with each key, row = position
        of the key in ``keys``."""
        lo = np.searchsorted(self.keys, keys, "left")
        n = np.searchsorted(self.keys, keys, "right") - lo
        rows = np.repeat(np.arange(len(keys)), n)
        first = np.repeat(lo - (np.cumsum(n) - n), n)
        return rows, self.values[first + np.arange(len(rows))]


class Graph:
    """Per-predicate indexes by subject and by object, built on first use,
    and the numeric value of every term (NaN where it is not a number)."""

    def __init__(self, ds: Dataset):
        self.ds = ds
        self._by_s: Dict[int, Index] = {}
        self._by_o: Dict[int, Index] = {}
        self._pairs_sorted: Dict[int, np.ndarray] = {}
        self.number = np.array(
            [np.nan if isinstance(t, str) else float(t) for t in ds.terms])

    def _pairs(self, pred: str) -> Tuple[np.ndarray, np.ndarray]:
        rows = self.ds.spo[self.ds.spo[:, 1] == self.ds.pred(pred)]
        return rows[:, 0], rows[:, 2]

    def by_s(self, pred: str) -> Index:
        p = self.ds.pred(pred)
        if p not in self._by_s:
            s, o = self._pairs(pred)
            self._by_s[p] = Index(s, o)
        return self._by_s[p]

    def by_o(self, pred: str) -> Index:
        p = self.ds.pred(pred)
        if p not in self._by_o:
            s, o = self._pairs(pred)
            self._by_o[p] = Index(o, s)
        return self._by_o[p]

    def has(self, pred: str, s: np.ndarray, o: np.ndarray) -> np.ndarray:
        """Whether each (s[i], pred, o[i]) is a triple of the graph."""
        p = self.ds.pred(pred)
        if p not in self._pairs_sorted:
            idx = self.by_s(pred)
            self._pairs_sorted[p] = np.sort(
                (idx.keys.astype(np.int64) << 32) | idx.values.astype(np.int64))
        packed = self._pairs_sorted[p]
        want = (s.astype(np.int64) << 32) | o.astype(np.int64)
        at = np.minimum(np.searchsorted(packed, want), max(len(packed) - 1, 0))
        return (packed[at] == want) if len(packed) else np.zeros(len(want), bool)


def extend(rows: Rows, index: Index, key: str, out: str,
           optional: bool = False) -> Rows:
    """Join ``rows`` with a triple pattern whose bound end is ``key``:
    one row for each match, the match in column ``out``. ``optional``
    keeps a row with no match once, with ``out`` unbound (-1)."""
    r, v = index.expand(rows[key])
    if optional:
        missing = np.setdiff1d(np.arange(len(rows[key])), r)
        r = np.concatenate([r, missing])
        v = np.concatenate([v, np.full(len(missing), -1, v.dtype)])
    new = {k: a[r] for k, a in rows.items()}
    new[out] = v
    return new


def where(rows: Rows, keep: np.ndarray) -> Rows:
    return {k: a[keep] for k, a in rows.items()}


def n_rows(rows: Rows) -> int:
    return len(next(iter(rows.values())))
