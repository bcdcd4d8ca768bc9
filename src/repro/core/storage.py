"""Sorted quad storage (paper §2.2.1).

Stardog stores RDF quads as lexicographically sorted collections of four
64-bit numbers in several orders, backed by RocksDB, and scans support a
``skip()`` (seek) to the next row with key >= target. Here the storage tier
is in-memory: each index is an (N, 4) int32 array sorted lexicographically
by its permutation, and ``skip()`` is a staged binary search. The scan API
(`range_for_pattern`, `read`, `seek`) preserves seek/range semantics so a
disk tier could slot underneath without touching the engine.

Index selection mirrors Stardog: not all 24 permutations are kept — SPOC,
POSC and OSPC cover every bound-prefix combination a triple pattern needs
(subject-bound, predicate-bound, object-bound), with CSPO optional for named
graphs.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.dictionary import Dictionary, Term

# column roles in a quad
S, P, O, C = 0, 1, 2, 3

INDEX_ORDERS: Dict[str, Tuple[int, int, int, int]] = {
    "spoc": (S, P, O, C),
    "posc": (P, O, S, C),
    "ospc": (O, S, P, C),
    # predicate-subject order: lets ?s <p> ?o scans come out sorted by
    # subject, which is what BGP merge joins on subjects want.
    "psoc": (P, S, O, C),
}


def _lexsort_rows(arr: np.ndarray) -> np.ndarray:
    # np.lexsort sorts by last key first
    order = np.lexsort((arr[:, 3], arr[:, 2], arr[:, 1], arr[:, 0]))
    return arr[order]


@dataclasses.dataclass
class ScanRange:
    """A contiguous row range [lo, hi) within one index."""

    index: str
    lo: int
    hi: int

    def __len__(self) -> int:
        return self.hi - self.lo


class QuadStore:
    """In-memory sorted quad indexes + dictionary."""

    def __init__(self, dictionary: Optional[Dictionary] = None) -> None:
        self.dict = dictionary or Dictionary()
        self._indexes: Dict[str, np.ndarray] = {}
        # contiguous per-column copies of each index: searchsorted on a
        # strided column view of the (N, 4) C-order array copies the whole
        # column before binary-searching, turning every range_for_pattern /
        # seek into an O(N) memcpy instead of an O(log N) probe.
        self._index_cols: Dict[str, list] = {}
        self._pending: list = []
        self.n_quads = 0

    # -- loading -------------------------------------------------------------

    def add(self, s: Term, p: Term, o: Term, g: Term = ":default") -> None:
        self._pending.append(
            (
                self.dict.encode(s),
                self.dict.encode(p),
                self.dict.encode(o),
                self.dict.encode(g),
            )
        )

    def add_encoded(self, quads: np.ndarray) -> None:
        """Bulk-add already-encoded (N, 4) int32 quads."""
        self._pending.append(np.asarray(quads, dtype=np.int32))

    def build(self) -> "QuadStore":
        """Sort and freeze the indexes (file-ingestion analogue)."""
        parts = []
        for item in self._pending:
            if isinstance(item, np.ndarray):
                parts.append(item.reshape(-1, 4))
            else:
                parts.append(np.asarray([item], dtype=np.int32))
        raw = (
            np.concatenate(parts, axis=0)
            if parts
            else np.zeros((0, 4), dtype=np.int32)
        )
        self._pending = []
        # dedupe (RDF graphs are sets of triples)
        raw = np.unique(raw, axis=0)
        self.n_quads = len(raw)
        for name, perm in INDEX_ORDERS.items():
            idx = _lexsort_rows(raw[:, list(perm)])
            self._indexes[name] = idx
            self._index_cols[name] = [
                np.ascontiguousarray(idx[:, i]) for i in range(4)
            ]
        return self

    # -- pattern evaluation ----------------------------------------------------

    def index_array(self, name: str) -> np.ndarray:
        return self._indexes[name]

    def choose_index(
        self, bound: Sequence[Optional[int]], want_sorted_role: Optional[int]
    ) -> str:
        """Pick the index whose order puts bound roles first and the desired
        output-sort role next. ``bound`` is (s, p, o, c) with None = free."""
        best, best_score = "spoc", -1
        for name, perm in INDEX_ORDERS.items():
            score = 0
            i = 0
            # bound roles must form a prefix of the index order
            while i < 4 and bound[perm[i]] is not None:
                score += 4
                i += 1
            n_bound = sum(b is not None for b in bound)
            if score // 4 < n_bound:
                continue  # some bound role is not in the prefix: unusable
            if want_sorted_role is not None and i < 4 and perm[i] == want_sorted_role:
                score += 2
            if score > best_score:
                best, best_score = name, score
        if best_score < 0:
            # no index has all bound roles in prefix — fall back to spoc with
            # post-filtering (engine handles residual equality checks)
            return "spoc"
        return best

    def range_for_pattern(
        self, index: str, bound: Sequence[Optional[int]]
    ) -> ScanRange:
        """Binary-search the row range matching the bound prefix."""
        cols = self._index_cols[index]
        perm = INDEX_ORDERS[index]
        lo, hi = 0, len(self._indexes[index])
        for col_pos in range(4):
            role = perm[col_pos]
            v = bound[role]
            if v is None:
                break
            col = cols[col_pos][lo:hi]  # contiguous 1-D slice: O(log N)
            # needle must match the column dtype: a Python-int needle makes
            # numpy promote and cast the whole column (O(N)) before searching
            v = np.int32(v)
            lo_off = np.searchsorted(col, v, side="left")
            hi_off = np.searchsorted(col, v, side="right")
            lo, hi = lo + int(lo_off), lo + int(hi_off)
        return ScanRange(index, lo, hi)

    def read(self, rng: ScanRange, start: int, count: int) -> np.ndarray:
        """Read up to ``count`` rows at offset ``start`` within the range.
        Rows come back in index order (permuted columns)."""
        lo = rng.lo + start
        hi = min(lo + count, rng.hi)
        return self._indexes[rng.index][lo:hi]

    def seek(self, rng: ScanRange, start: int, sort_col_pos: int, target: int,
             side: str = "left") -> int:
        """skip(): offset (>= start) of first row whose key at ``sort_col_pos``
        within the index order is >= target (> target with ``side="right"``).
        This is the RocksDB seek analogue the BARQ merge join drives (paper
        §3.2 Skip phase)."""
        col = self._index_cols[rng.index][sort_col_pos][rng.lo + start : rng.hi]
        return start + int(np.searchsorted(col, np.int32(target), side=side))

    def seek_keys(self, rng: ScanRange, start: int, stop: int, sort_col_pos: int,
                  keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """seek() for many sorted keys at once: the offsets (in [start,
        stop)) of the first row of each key's run in the sort column and
        of the row after its last. A key with no rows gets an empty run."""
        col = self._index_cols[rng.index][sort_col_pos][rng.lo + start : rng.lo + stop]
        keys = np.asarray(keys, dtype=col.dtype)
        return (start + np.searchsorted(col, keys, side="left"),
                start + np.searchsorted(col, keys, side="right"))

    def take(self, rng: ScanRange, offsets: np.ndarray) -> np.ndarray:
        """The rows at ``offsets`` within the range, in index order
        (permuted columns), as a copy."""
        return self._indexes[rng.index][rng.lo + offsets]

    # -- stats for the optimizer ------------------------------------------------

    def pattern_cardinality(self, bound: Sequence[Optional[int]]) -> int:
        idx = self.choose_index(bound, None)
        return len(self.range_for_pattern(idx, bound))
