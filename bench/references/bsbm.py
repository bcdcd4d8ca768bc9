"""Plain reference answers of the BSBM explore queries
(``bench/queries/bsbm.json``), written as numpy joins over the
benchmark's own triples. Each function follows its query's text: a triple
pattern becomes an index expansion or a membership test, FILTER a mask,
OPTIONAL a left join (a block with no match gives one unbound row), UNION
a concatenation. ORDER BY and LIMIT are left to the comparison
(``bench/harness/check.py``), which takes every row here as a candidate."""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench.harness.relational import Graph, Index, Rows, extend, n_rows, where


def cross(a: Rows, b: Rows) -> Rows:
    """Every row of ``a`` with every row of ``b``."""
    na, nb = n_rows(a), n_rows(b)
    out = {k: np.repeat(v, nb) for k, v in a.items()}
    out.update({k: np.tile(v, na) for k, v in b.items()})
    return out


def left(rows: Rows, block: Rows) -> Rows:
    """``rows`` joined with an OPTIONAL block that shares only constants
    with it: each row with each match, or once unbound where none."""
    if n_rows(block) == 0:
        block = {k: np.full(1, -1, np.int32) for k in block}
    return cross(rows, block)


class Reference:
    def __init__(self, ds):
        self.g = Graph(ds)
        self.ds = ds
        self.terms = ds.terms
        rows = np.arange(len(ds.spo))
        self.by_subject = Index(ds.spo[:, 0], rows)
        self.by_object = Index(ds.spo[:, 2], rows)

    def answer(self, query: str, bind: Dict[str, int]) -> List[tuple]:
        return getattr(self, query)(bind)

    def _rows(self, rows: Rows, cols: Sequence[str]) -> List[tuple]:
        if not n_rows(rows):
            return []
        t = self.terms
        return list(zip(*([None if c < 0 else t[c] for c in rows[v]] for v in cols)))

    def _one(self, name: str, code: int) -> Rows:
        return {name: np.array([code], np.int32)}

    def _obj(self, rows: Rows, pred: str, s: str, out: str, optional: bool = False) -> Rows:
        return extend(rows, self.g.by_s(pred), s, out, optional)

    def _has(self, rows: Rows, pred: str, s: str, o) -> Rows:
        """Rows where (s, pred, o) is a triple; ``o`` a column or a term."""
        obj = rows[o] if isinstance(o, str) and o in rows else np.full(
            n_rows(rows), self.ds.codes.get(o, -1), np.int32)
        return where(rows, self.g.has(pred, rows[s], obj))

    # -- the explore mix ---------------------------------------------------------

    def q2(self, b):
        rows = self._one("product", b["%PRODUCT%"])
        for pred, col in (("rdfs:label", "label"), ("rdfs:comment", "comment"),
                          ("bsbm:producer", "p")):
            rows = self._obj(rows, pred, "product", col)
        rows = self._obj(rows, "rdfs:label", "p", "producer")
        rows = self._has(rows, "dc:publisher", "product", "p")
        rows = self._obj(rows, "bsbm:productFeature", "product", "f")
        rows = self._obj(rows, "rdfs:label", "f", "productFeature")
        for k in (1, 2, 3):
            rows = self._obj(rows, f"bsbm:productPropertyTextual{k}", "product", f"t{k}")
        for k in (1, 2):
            rows = self._obj(rows, f"bsbm:productPropertyNumeric{k}", "product", f"n{k}")
        rows = self._obj(rows, "bsbm:productPropertyTextual4", "product", "t4", True)
        rows = self._obj(rows, "bsbm:productPropertyTextual5", "product", "t5", True)
        rows = self._obj(rows, "bsbm:productPropertyNumeric4", "product", "n4", True)
        return self._rows(rows, ("label", "comment", "producer", "productFeature", "t1", "t2",
                                 "t3", "n1", "n2", "t4", "t5", "n4"))

    def q7(self, b):
        g, product = self.g, b["%PRODUCT%"]
        rows = self._obj(self._one("product", product), "rdfs:label", "product", "productLabel")
        offers = {"offer": g.by_o("bsbm:product").lookup(product)}
        offers = self._obj(offers, "bsbm:price", "offer", "price")
        offers = self._obj(offers, "bsbm:vendor", "offer", "vendor")
        offers = self._obj(offers, "rdfs:label", "vendor", "vendorTitle")
        offers = self._has(offers, "bsbm:country", "vendor", "countries:DE")
        offers = self._has(offers, "dc:publisher", "offer", "vendor")
        offers = self._obj(offers, "bsbm:validTo", "offer", "date")
        offers = where(offers, g.number[offers["date"]] > b["%CURRENT_DATE%"])
        reviews = {"review": g.by_o("bsbm:reviewFor").lookup(product)}
        reviews = self._obj(reviews, "rev:reviewer", "review", "reviewer")
        reviews = self._obj(reviews, "foaf:name", "reviewer", "revName")
        reviews = self._obj(reviews, "dc:title", "review", "revTitle")
        reviews = self._obj(reviews, "bsbm:rating1", "review", "rating1", True)
        reviews = self._obj(reviews, "bsbm:rating2", "review", "rating2", True)
        rows = left(left(rows, offers), reviews)
        return self._rows(rows, ("productLabel", "offer", "price", "vendor", "vendorTitle",
                                 "review", "revTitle", "reviewer", "revName", "rating1",
                                 "rating2"))

    def q8(self, b):
        rows = {"review": self.g.by_o("bsbm:reviewFor").lookup(b["%PRODUCT%"])}
        for pred, col in (("dc:title", "title"), ("rev:text", "text"),
                          ("bsbm:reviewDate", "reviewDate"), ("rev:reviewer", "reviewer")):
            rows = self._obj(rows, pred, "review", col)
        rows = self._obj(rows, "foaf:name", "reviewer", "reviewerName")
        for k in (1, 2, 3, 4):
            rows = self._obj(rows, f"bsbm:rating{k}", "review", f"rating{k}", True)
        return self._rows(rows, ("title", "text", "reviewDate", "reviewer", "reviewerName",
                                 "rating1", "rating2", "rating3", "rating4"))

    def q9(self, b):
        rows = self._obj(self._one("review", b["%REVIEW%"]), "rev:reviewer", "review", "x")
        rows = extend(rows, self.by_subject, "x", "triple")
        spo = self.ds.spo[rows["triple"]]
        return self._rows(dict(rows, property=spo[:, 1], value=spo[:, 2]),
                          ("x", "property", "value"))

    def q10(self, b):
        g = self.g
        rows = {"offer": g.by_o("bsbm:product").lookup(b["%PRODUCT%"])}
        rows = self._obj(rows, "bsbm:vendor", "offer", "vendor")
        rows = self._has(rows, "dc:publisher", "offer", "vendor")
        rows = self._has(rows, "bsbm:country", "vendor", "countries:US")
        rows = self._obj(rows, "bsbm:deliveryDays", "offer", "days")
        rows = where(rows, g.number[rows["days"]] <= 3)
        rows = self._obj(rows, "bsbm:price", "offer", "price")
        rows = self._obj(rows, "bsbm:validTo", "offer", "date")
        rows = where(rows, g.number[rows["date"]] > b["%CURRENT_DATE%"])
        return self._rows(rows, ("offer", "price"))

    def q11(self, b):
        spo, offer = self.ds.spo, b["%OFFER%"]
        out = spo[self.by_subject.lookup(offer)]
        inc = spo[self.by_object.lookup(offer)]
        none = lambda n: np.full(n, -1, np.int32)  # noqa: E731
        rows = {"property": np.r_[out[:, 1], inc[:, 1]],
                "hasValue": np.r_[out[:, 2], none(len(inc))],
                "isValueOf": np.r_[none(len(out)), inc[:, 0]]}
        return self._rows(rows, ("property", "hasValue", "isValueOf"))

    def q12(self, b):
        rows = self._one("offer", b["%OFFER%"])
        rows = self._obj(rows, "bsbm:product", "offer", "productURI")
        rows = self._obj(rows, "rdfs:label", "productURI", "productlabel")
        rows = self._obj(rows, "bsbm:vendor", "offer", "vendorURI")
        rows = self._obj(rows, "rdfs:label", "vendorURI", "vendorname")
        rows = self._obj(rows, "foaf:homepage", "vendorURI", "vendorhomepage")
        for pred, col in (("bsbm:offerWebpage", "offerURL"), ("bsbm:price", "price"),
                          ("bsbm:deliveryDays", "deliveryDays"),
                          ("bsbm:validTo", "validTo")):
            rows = self._obj(rows, pred, "offer", col)
        return self._rows(rows, ("productURI", "productlabel", "vendorURI", "vendorname",
                                 "vendorhomepage", "offerURL", "price", "deliveryDays",
                                 "validTo"))
