"""The benchmark's own copy of a generated store: its terms, its triples
as codes into those terms, and named pools of entities that traffic draws
constants from. The reference answers from this copy; the system under
test gets the same triples through its public loading API."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

Term = Union[str, int, float]

GRAPH = ":default"


class TermTable:
    """Insertion-ordered term -> code table (codes are list positions)."""

    def __init__(self) -> None:
        self.terms: List[Term] = []
        self._code: Dict[Term, int] = {}

    def add(self, term: Term) -> int:
        code = self._code.get(term)
        if code is None:
            code = len(self.terms)
            self._code[term] = code
            self.terms.append(term)
        return code

    def add_many(self, terms: Sequence[Term]) -> np.ndarray:
        return np.fromiter((self.add(t) for t in terms), dtype=np.int32,
                           count=len(terms))

    def code(self, term: Term) -> Optional[int]:
        return self._code.get(term)


@dataclasses.dataclass
class Dataset:
    terms: List[Term]
    codes: Dict[Term, int]
    spo: np.ndarray  # (n, 3) int32 codes into ``terms``
    pools: Dict[str, np.ndarray]  # name -> int32 codes
    sizes: Dict[str, int]  # what the generator reports

    @classmethod
    def from_parts(cls, table: TermTable, parts: Sequence[np.ndarray],
                   pools: Dict[str, np.ndarray], sizes: Dict[str, int]) -> "Dataset":
        # an RDF graph is a set of triples
        spo = np.unique(np.concatenate(parts, axis=0).astype(np.int32), axis=0)
        sizes = dict(sizes, triples=int(len(spo)), terms=len(table.terms))
        return cls(table.terms, table._code, spo, pools, sizes)

    def pred(self, term: Term) -> int:
        return self.codes[term]

    def text(self, code: int) -> str:
        """The term as it is written in a query."""
        t = self.terms[code]
        return t if isinstance(t, str) else repr(t)

    def load(self):
        """The same triples in the system under test's store, through its
        public loading API (dictionary encode, bulk add, build)."""
        from repro.core.storage import QuadStore

        store = QuadStore()
        prog = store.dict.encode_many(self.terms)
        g = store.dict.encode(GRAPH)
        quads = np.empty((len(self.spo), 4), dtype=np.int32)
        quads[:, :3] = prog[self.spo]
        quads[:, 3] = g
        store.add_encoded(quads)
        store.build()
        return store
