"""Plain reference answers of LSQB's nine counts (``queries/lsqb.json``)
over the benchmark's own triples, in numpy alone: joins as row expansion
where the rows stay few, else per-term group-by counts, summed exactly in
int64. Each answer is one row, the count. ``acc`` is the dtype the counts
are summed in, one term after another: int64 for the answers, float32 for
the ``float32_sums`` control."""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.harness.relational import Graph, extend, where


class Reference:
    def __init__(self, ds, acc=np.int64):
        self.ds = ds
        self.g = Graph(ds)
        self.acc = acc

    def answer(self, query: str, bind: Dict[str, int]) -> List[tuple]:
        return [(int(getattr(self, query)()),)]

    # -- relational steps -----------------------------------------------------

    def edges(self, pred: str):
        idx = self.g.by_s(pred)
        return {"s": idx.keys, "o": idx.values}

    def per_term(self, keys: np.ndarray, weights=None) -> np.ndarray:
        """Σ of ``weights`` (1 each if None) by term code."""
        out = np.zeros(len(self.ds.terms), dtype=self.acc)
        np.add.at(out, keys, 1 if weights is None else weights.astype(self.acc))
        return out

    def total(self, terms: np.ndarray) -> int:
        """Σ of ``terms`` (a mask counts its rows), added one after another."""
        return int(np.cumsum(terms, dtype=self.acc)[-1]) if len(terms) else 0

    def out_degree(self, pred: str) -> np.ndarray:
        return self.per_term(self.edges(pred)["s"])

    def in_degree(self, pred: str) -> np.ndarray:
        return self.per_term(self.edges(pred)["o"])

    def two_hop(self):
        """?p1 knows ?p2 . ?p2 knows ?p3, with ?p1 != ?p3."""
        knows = self.g.by_s("sn:knows")
        rows = extend({"p1": knows.keys, "p2": knows.values}, knows, "p2", "p3")
        return where(rows, rows["p1"] != rows["p3"])

    # -- the queries ------------------------------------------------------------

    def q1(self):
        # persons' (city, country) chains, forums' members weighted by them
        part_of = self.out_degree("sn:isPartOf")
        located = self.edges("sn:isLocatedIn")
        chains = self.per_term(located["s"], part_of[located["o"]])
        member = self.edges("sn:hasMember")
        members = self.per_term(member["s"], chains[member["o"]])
        # comments' tags weighted by their classes, up to posts and forums
        tagged = self.edges("sn:hasTag")
        tags = self.per_term(tagged["s"], self.out_degree("sn:hasType")[tagged["o"]])
        reply = self.edges("sn:replyOf")
        posts = self.per_term(reply["o"], tags[reply["s"]])
        contains = self.edges("sn:containerOf")
        forums = self.per_term(contains["s"], posts[contains["o"]])
        return self.total(members * forums)

    def q2(self):
        created = self.edges("sn:hasCreator")
        rows = {"comment": created["s"], "p1": created["o"]}
        rows = extend(rows, self.g.by_s("sn:replyOf"), "comment", "post")
        post_type = np.full(len(rows["post"]), self.ds.codes["sn:Post"], dtype=np.int32)
        rows = where(rows, self.g.has("rdf:type", rows["post"], post_type))
        rows = extend(rows, self.g.by_s("sn:hasCreator"), "post", "p2")
        return self.total(self.g.has("sn:knows", rows["p1"], rows["p2"]))

    def q3(self):
        rows = self.two_hop()
        rows = where(rows, self.g.has("sn:knows", rows["p3"], rows["p1"]))
        located, part_of = self.g.by_s("sn:isLocatedIn"), self.g.by_s("sn:isPartOf")
        rows = extend(extend(rows, located, "p1", "c1"), part_of, "c1", "country")
        for p in ("p2", "p3"):
            rows = extend(extend(rows, located, p, "c"), part_of, "c", "k")
            rows = where(rows, rows["k"] == rows["country"])
        return self.total(np.ones(len(rows["country"]), dtype=bool))

    def _q4(self, optional: bool) -> int:
        likes, replies = self.in_degree("sn:likes"), self.in_degree("sn:replyOf")
        if optional:
            likes, replies = np.maximum(likes, 1), np.maximum(replies, 1)
        per = (self.out_degree("sn:hasTag") * self.out_degree("sn:hasCreator")
               * likes * replies)
        return self.total(per)

    def q4(self):
        return self._q4(optional=False)

    def q7(self):
        return self._q4(optional=True)

    def _q5(self):
        reply = self.edges("sn:replyOf")
        tags = self.g.by_s("sn:hasTag")
        rows = extend({"comment": reply["s"], "message": reply["o"]}, tags, "message", "tag1")
        rows = extend(rows, tags, "comment", "tag2")
        return where(rows, rows["tag1"] != rows["tag2"])

    def q5(self):
        return self.total(np.ones(len(self._q5()["tag1"]), dtype=bool))

    def q8(self):
        rows = self._q5()
        return self.total(~self.g.has("sn:hasTag", rows["comment"], rows["tag1"]))

    def _q6(self, minus: bool) -> int:
        rows = self.two_hop()
        if minus:
            rows = where(rows, ~self.g.has("sn:knows", rows["p1"], rows["p3"]))
        return self.total(self.out_degree("sn:hasInterest")[rows["p3"]])

    def q6(self):
        return self._q6(minus=False)

    def q9(self):
        return self._q6(minus=True)
