"""BSBM e-commerce store (Bizer & Schultz, "The Berlin SPARQL Benchmark",
2009, and the BSBM 3.1 specification's data generator): a tree of product
types with their features; products of a leaf type, each with a label, a
comment, a producer, features of its type, numeric and textual properties;
producers and vendors; offers of products by vendors; reviewers; reviews
of products by reviewers, with a title, a text, a date and ratings. Every
instance carries its ``dc:publisher`` and ``dc:date``, as in BSBM.

Counts per class come from the configuration (``products``,
``product_types``, ...); how many offers and reviews a product has, and
how many features, properties and words each record carries, from its
per-record keys. Draws are uniform, as in the BSBM generator, except where
a query's work hangs on a count: every product has exactly its offers and
reviews, the countries are dealt to vendors, producers and reviewers in
equal shares, and a product's offers to the countries' vendors in equal
shares, in rounds (below), in an order the seed draws. So what the queries
join on has the same sizes on every seed, and a request of one query costs
about the same whatever its constant and the seed. Literals are
written as in a query: strings quoted, numbers as numbers, dates as
numbers ``yyyymmdd``. This is the benchmark's own generator: nothing here
comes from the system under test."""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.harness.dataset import Dataset, TermTable

LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))
COUNTRIES = ("US", "GB", "DE", "FR", "JP", "CN", "RU", "ES", "AT", "KR")


def _vocabulary(rng, n: int) -> np.ndarray:
    """``n`` distinct lowercase words of 3 to 10 letters."""
    out: List[str] = []
    seen = set()
    while len(out) < n:
        for length in rng.integers(3, 11, n):
            w = "".join(LETTERS[rng.integers(0, 26, length)])
            if w not in seen:
                seen.add(w)
                out.append(w)
                if len(out) == n:
                    break
    return np.array(out, dtype=object)


def _texts(rng, vocab: np.ndarray, n: int, lo: int, hi: int, quote: bool = True) -> List[str]:
    """``n`` strings of ``lo`` to ``hi`` words drawn from ``vocab``."""
    counts = rng.integers(lo, hi + 1, n)
    words = vocab[rng.integers(0, len(vocab), int(counts.sum()))]
    ends = np.cumsum(counts)
    starts = ends - counts
    q = '"' if quote else ""
    return [q + " ".join(words[a:b]) + q for a, b in zip(starts, ends)]


def _shares(rng, n: int, values: np.ndarray) -> np.ndarray:
    """``n`` draws of ``values`` that take each value equally often (the
    first ones once more where ``n`` does not divide), in a random order."""
    return values[rng.permutation(np.arange(n) % len(values))]


def _dates(rng, n: int, first: str, days: int) -> np.ndarray:
    """``n`` dates as numbers yyyymmdd, uniform over ``days`` days from ``first``."""
    d = np.datetime64(first) + rng.integers(0, days, n).astype("timedelta64[D]")
    return _yyyymmdd(d)


def _yyyymmdd(d: np.ndarray) -> np.ndarray:
    s = np.datetime_as_string(d, unit="D")
    return np.char.replace(s.astype(str), "-", "").astype(np.int64)


def generate(params: Dict[str, int], seed: int) -> Dataset:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    p = params
    n_product, n_type, n_feature = p["products"], p["product_types"], p["product_features"]
    n_producer, n_vendor, n_person = p["producers"], p["vendors"], p["reviewers"]
    n_site = p["rating_sites"]
    n_offer = n_product * p["offers_per_product"]
    n_review = n_product * p["reviews_per_product"]
    vocab = _vocabulary(rng, p["vocabulary"])

    t = TermTable()
    parts: List[np.ndarray] = []
    P = {name: t.add(name) for name in (
        "rdf:type", "rdfs:label", "rdfs:comment", "rdfs:subClassOf", "dc:publisher",
        "dc:date", "dc:title", "foaf:homepage", "foaf:name", "foaf:mbox_sha1sum",
        "bsbm:country", "bsbm:producer", "bsbm:productFeature", "bsbm:product",
        "bsbm:vendor", "bsbm:price", "bsbm:validFrom", "bsbm:validTo",
        "bsbm:deliveryDays", "bsbm:offerWebpage", "bsbm:reviewFor", "rev:reviewer",
        "bsbm:reviewDate", "rev:text")}
    for k in range(1, 7):
        P[f"num{k}"] = t.add(f"bsbm:productPropertyNumeric{k}")
        P[f"text{k}"] = t.add(f"bsbm:productPropertyTextual{k}")
    for k in range(1, 5):
        P[f"rating{k}"] = t.add(f"bsbm:rating{k}")

    def emit(s, pred, o):
        s = np.asarray(s, np.int32)
        parts.append(np.stack([s, np.full(len(s), P[pred], np.int32),
                               np.broadcast_to(np.asarray(o, np.int32), s.shape)], axis=1))

    def literals(values) -> np.ndarray:
        return t.add_many(list(values))

    def entities(prefix: str, n: int) -> np.ndarray:
        return t.add_many([f"{prefix}{i}" for i in range(1, n + 1)])

    def describe(ent, cls, n, publisher, label=True, comment=True):
        """Type, label, comment, publisher and date of every entity."""
        emit(ent, "rdf:type", t.add(cls))
        if label:
            emit(ent, "rdfs:label", literals(_texts(rng, vocab, n, 1, 3)))
        if comment:
            emit(ent, "rdfs:comment", literals(_texts(
                rng, vocab, n, p["comment_words_min"], p["comment_words_max"])))
        emit(ent, "dc:publisher", publisher)
        emit(ent, "dc:date", literals(_dates(rng, n, p["first_date"], p["date_days"]).tolist()))

    country = np.array([t.add(f"countries:{c}") for c in COUNTRIES], np.int32)

    # product types: a tree with ``type_branching`` children a node; the
    # leaves are the types products have
    types = entities("bsbm-inst:ProductType", n_type)
    parent = (np.arange(1, n_type) - 1) // p["type_branching"]
    emit(types[1:], "rdfs:subClassOf", types[parent])
    describe(types, "bsbm:ProductType", n_type, t.add("bsbm-inst:StandardizationInstitution1"))
    leaf_index = np.setdiff1d(np.arange(n_type), parent)

    # features, each belonging to one type; a product draws its features
    # from its leaf type and the leaf's ancestors
    features = entities("bsbm-inst:ProductFeature", n_feature)
    describe(features, "bsbm:ProductFeature", n_feature,
             t.add("bsbm-inst:StandardizationInstitution1"))
    feature_type = rng.integers(0, n_type, n_feature)
    by_type = np.argsort(feature_type, kind="stable")
    type_lo = np.searchsorted(feature_type[by_type], np.arange(n_type))
    type_n = np.bincount(feature_type, minlength=n_type)
    up = np.r_[-1, parent]
    path = [np.arange(n_type)]
    while (path[-1] >= 0).any():
        path.append(np.where(path[-1] >= 0, up[np.maximum(path[-1], 0)], -1))
    path = np.stack(path, 1)  # (type, its ancestors from itself up), -1 past the root
    path_n = np.where(path >= 0, type_n[np.maximum(path, 0)], 0)
    path_cum = np.cumsum(path_n, 1)

    producers = entities("bsbm-inst:Producer", n_producer)
    describe(producers, "bsbm:Producer", n_producer, producers)
    emit(producers, "foaf:homepage", literals(f'"http://www.producer{i}.com/"'
                                              for i in range(1, n_producer + 1)))
    emit(producers, "bsbm:country", _shares(rng, n_producer, country))

    vendors = entities("bsbm-inst:Vendor", n_vendor)
    describe(vendors, "bsbm:Vendor", n_vendor, vendors)
    emit(vendors, "foaf:homepage", literals(f'"http://www.vendor{i}.com/"'
                                            for i in range(1, n_vendor + 1)))
    vendor_country = _shares(rng, n_vendor, np.arange(len(country)))
    emit(vendors, "bsbm:country", country[vendor_country])

    # products
    products = entities("bsbm-inst:Product", n_product)
    producer_of = producers[rng.integers(0, n_producer, n_product)]
    emit(products, "rdf:type", t.add("bsbm:Product"))
    leaf = leaf_index[rng.integers(0, len(leaf_index), n_product)]
    emit(products, "rdf:type", types[leaf])
    emit(products, "rdfs:label", literals(_texts(rng, vocab, n_product, 1, 3)))
    emit(products, "rdfs:comment", literals(_texts(
        rng, vocab, n_product, p["comment_words_min"], p["comment_words_max"])))
    emit(products, "bsbm:producer", producer_of)
    emit(products, "dc:publisher", producer_of)
    emit(products, "dc:date", literals(_dates(rng, n_product, p["first_date"],
                                              p["date_days"]).tolist()))
    want = rng.integers(p["features_per_product_min"], p["features_per_product_max"] + 1,
                        n_product)
    owner = np.repeat(np.arange(n_product), want)
    lt = leaf[owner]
    r = (rng.random(len(owner)) * path_cum[lt, -1]).astype(np.int64)
    k = (r[:, None] >= path_cum[lt]).sum(1)
    node = path[lt, k]
    offset = r - (path_cum[lt, k] - path_n[lt, k])
    pf = np.unique(np.stack([owner, features[by_type[type_lo[node] + offset]]], 1), axis=0)
    emit(products[pf[:, 0]], "bsbm:productFeature", pf[:, 1])
    for k in range(1, 7):
        has = np.arange(n_product) if k <= 3 else np.flatnonzero(
            rng.random(n_product) < p["optional_property_share"])
        emit(products[has], f"num{k}", literals(
            rng.integers(1, p["max_numeric"] + 1, len(has)).tolist()))
        has = np.arange(n_product) if k <= 3 else np.flatnonzero(
            rng.random(n_product) < p["optional_property_share"])
        emit(products[has], f"text{k}", literals(_texts(
            rng, vocab, len(has), p["textual_words_min"], p["textual_words_max"])))

    # offers, numbered in rounds: each round holds one offer of every
    # product, in an order of its own, and every offer of a round comes from
    # a vendor of the round's country (a vendor of that country drawn
    # uniformly). The rounds deal the vendors' countries in turn, so a
    # product's offers come from every country's vendors in equal shares,
    # and its offers from one country lie equally far apart, whatever the
    # product
    offers = entities("bsbm-inst:Offer", n_offer)
    rounds = p["offers_per_product"]
    by_country = np.argsort(vendor_country, kind="stable")
    country_n = np.bincount(vendor_country, minlength=len(country))
    country_lo = np.cumsum(country_n) - country_n
    held = rng.permutation(np.flatnonzero(country_n))
    offer_product = np.concatenate([rng.permutation(n_product) for _ in range(rounds)])
    offer_country = np.repeat(held[np.arange(rounds) % len(held)], n_product)
    vendor_of = vendors[by_country[country_lo[offer_country] + (
        rng.random(n_offer) * country_n[offer_country]).astype(np.int64)]]
    emit(offers, "rdf:type", t.add("bsbm:Offer"))
    emit(offers, "bsbm:product", products[offer_product])
    emit(offers, "bsbm:vendor", vendor_of)
    emit(offers, "dc:publisher", vendor_of)
    cents = rng.integers(500, 100 * p["max_price"] + 1, n_offer)
    emit(offers, "bsbm:price", literals((cents / 100.0).tolist()))
    start = np.datetime64(p["first_date"]) + rng.integers(
        0, p["date_days"], n_offer).astype("timedelta64[D]")
    length = rng.integers(p["valid_days_min"], p["valid_days_max"] + 1, n_offer)
    emit(offers, "bsbm:validFrom", literals(_yyyymmdd(start).tolist()))
    emit(offers, "bsbm:validTo", literals(
        _yyyymmdd(start + length.astype("timedelta64[D]")).tolist()))
    emit(offers, "bsbm:deliveryDays", literals(
        rng.integers(1, p["max_delivery_days"] + 1, n_offer).tolist()))
    emit(offers, "bsbm:offerWebpage", literals(
        f'"http://www.vendor{v}.com/offer{i}.html"' for i, v in
        zip(range(1, n_offer + 1), np.searchsorted(vendors, vendor_of) + 1)))
    emit(offers, "dc:date", literals(_dates(rng, n_offer, p["first_date"],
                                            p["date_days"]).tolist()))

    # reviewers and reviews
    sites = entities("bsbm-inst:RatingSite", n_site)
    people = entities("bsbm-inst:Reviewer", n_person)
    site_of_person = sites[rng.integers(0, n_site, n_person)]
    emit(people, "rdf:type", t.add("foaf:Person"))
    emit(people, "foaf:name", literals(_texts(rng, vocab, n_person, 1, 2)))
    emit(people, "foaf:mbox_sha1sum", literals(
        f'"{h:040x}"' for h in rng.integers(0, 2**62, n_person).tolist()))
    emit(people, "bsbm:country", _shares(rng, n_person, country))
    emit(people, "dc:publisher", site_of_person)
    emit(people, "dc:date", literals(_dates(rng, n_person, p["first_date"],
                                            p["date_days"]).tolist()))

    reviews = entities("bsbm-inst:Review", n_review)
    reviewer_of = rng.integers(0, n_person, n_review)
    emit(reviews, "rdf:type", t.add("bsbm:Review"))
    emit(reviews, "bsbm:reviewFor", _shares(rng, n_review, products))
    emit(reviews, "rev:reviewer", people[reviewer_of])
    emit(reviews, "bsbm:reviewDate", literals(_dates(rng, n_review, p["first_date"],
                                                     p["date_days"]).tolist()))
    emit(reviews, "dc:title", literals(_texts(rng, vocab, n_review, p["title_words_min"],
                                              p["title_words_max"])))
    emit(reviews, "rev:text", literals(_texts(rng, vocab, n_review, p["review_words_min"],
                                              p["review_words_max"])))
    for k in range(1, 5):
        has = np.flatnonzero(rng.random(n_review) < p["rating_share"])
        emit(reviews[has], f"rating{k}", literals(
            rng.integers(1, p["max_rating"] + 1, len(has)).tolist()))
    emit(reviews, "dc:publisher", site_of_person[reviewer_of])
    emit(reviews, "dc:date", literals(_dates(rng, n_review, p["first_date"],
                                             p["date_days"]).tolist()))

    pools = {"product": products, "offer": offers, "review": reviews}
    sizes = {"products": n_product, "offers": n_offer, "reviews": n_review,
             "product_types": n_type, "product_features": n_feature}
    return Dataset.from_parts(t, parts, pools, sizes)
