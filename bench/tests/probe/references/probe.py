"""Plain reference answers of the probe's count queries
(``queries/probe.json``), as numpy joins over the benchmark's own triples:
each counts the rows of its pattern, one row with the count."""

from __future__ import annotations

from typing import Dict, List

from bench.harness.relational import Graph, extend, n_rows, where


class Reference:
    def __init__(self, ds):
        self.g = Graph(ds)

    def answer(self, query: str, bind: Dict[str, int]) -> List[tuple]:
        return [(n_rows(getattr(self, query)()),)]

    def _paths(self):
        """?p1 knows ?p2 . ?p2 knows ?p3 . ?p3 hasInterest ?tag, ?p1 != ?p3."""
        knows = self.g.by_s("probe:knows")
        rows = {"p1": knows.keys, "p2": knows.values}
        rows = extend(rows, knows, "p2", "p3")
        rows = where(rows, rows["p1"] != rows["p3"])
        return extend(rows, self.g.by_s("probe:hasInterest"), "p3", "tag")

    def q6(self):
        return self._paths()

    def q9(self):
        """q6 less the paths whose ends know each other (MINUS)."""
        rows = self._paths()
        return where(rows, ~self.g.has("probe:knows", rows["p3"], rows["p1"]))
