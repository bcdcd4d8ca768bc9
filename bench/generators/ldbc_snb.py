"""An LDBC Social Network Benchmark graph as LSQB reads it (Mhedhbi et al.,
"LSQB: A Large-Scale Subgraph Query Benchmark", GRADES-NDA 2021, over the
graph LDBC's Datagen makes): nodes and edges, no attributes.

Places: continents ⊃ countries ⊃ cities (``sn:isPartOf``). Tag classes in
a tree (``sn:isSubclassOf``), tags of a class (``sn:hasType``).
Organisations: universities in cities, companies in countries. Persons,
each in a city of a country (``sn:isLocatedIn``), who know each other
(``sn:knows``, two triples an undirected edge, so that directed patterns
count what Cypher's undirected pattern counts), have interests (tags),
study and work at organisations. Forums with a moderator, members, tags
and a container of posts. Comments reply to posts or to comments
(``sn:replyOf``). Every message (post or comment, each also
``a sn:Message``) has a creator, a country (its creator's), tags and
likes.

How many of each thing, and each degree sequence, come from ``params``
alone: a sequence is the quantiles of a lognormal of the param's shape
(``*_sigma``; 0 is even), scaled to its total and capped. The seed draws
who: which entity gets which degree, and who is at the other end of each
edge, uniformly and without repeats. So the work a query does hangs on
``params``, while counts that hang on overlaps (a triangle's countries,
a reply's tags against its parent's) move a little with the seed. This is
the benchmark's own generator: nothing here comes from the system under
test."""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np
from scipy.special import ndtri

from bench.harness.dataset import Dataset, TermTable

PREDICATES = ("rdf:type", "sn:isPartOf", "sn:isSubclassOf", "sn:hasType", "sn:isLocatedIn",
              "sn:knows", "sn:hasInterest", "sn:studyAt", "sn:workAt", "sn:hasModerator",
              "sn:hasMember", "sn:hasTag", "sn:containerOf", "sn:hasCreator", "sn:replyOf",
              "sn:likes")


def degrees(n: int, total: int, sigma: float, cap: int) -> np.ndarray:
    """``n`` whole degrees summing to ``total``, each at most ``cap``: the
    quantiles of a lognormal of shape ``sigma``, scaled, rounded by
    largest remainder; in ascending order."""
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    if total > n * cap:
        raise ValueError(f"{total} edges do not fit {n} nodes of at most {cap}")
    x = np.exp(sigma * ndtri((np.arange(n) + 0.5) / n))
    x *= total / x.sum()
    for _ in range(64):  # hand what the cap cuts to the degrees below it
        over = x > cap
        if not over.any():
            break
        x[over] = cap
        free = x < cap
        x[free] *= (total - x[~free].sum()) / x[free].sum()
    d = np.floor(x).astype(np.int64)
    short = int(total - d.sum())
    if short:
        frac = np.where(d < cap, x - d, -1.0)
        d[np.argsort(-frac, kind="stable")[:short]] += 1
    return d


def dealt(rng, n: int, total: int, sigma: float, cap: int) -> np.ndarray:
    """``degrees`` dealt to ``n`` entities in an order the seed draws."""
    return degrees(n, total, sigma, cap)[rng.permutation(n)]


def picks(rng, counts: np.ndarray, n_values: int) -> Tuple[np.ndarray, np.ndarray]:
    """(owner, value) pairs, sorted by owner: owner ``i`` draws
    ``counts[i]`` distinct values of ``range(n_values)`` uniformly;
    repeats are drawn again."""
    owner = np.repeat(np.arange(len(counts)), counts)
    value = rng.integers(n_values, size=len(owner))
    for _ in range(1000):
        key = owner.astype(np.int64) * n_values + value
        order = np.argsort(key, kind="stable")
        bad = np.zeros(len(key), dtype=bool)
        bad[order[1:]] = key[order[1:]] == key[order[:-1]]
        if not bad.any():
            return owner, value
        value[bad] = rng.integers(n_values, size=int(bad.sum()))
    raise RuntimeError("could not draw distinct values")


def knows_edges(rng, deg: np.ndarray) -> np.ndarray:
    """An undirected simple graph with exactly the degrees ``deg``, as
    (m, 2): stubs paired in an order the seed draws, then each self-loop
    or repeated pair rewired by swapping an end with a random edge."""
    stubs = rng.permutation(np.repeat(np.arange(len(deg)), deg))
    e = stubs.reshape(-1, 2).copy()
    n = len(deg)
    for _ in range(10000):
        lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
        key = lo.astype(np.int64) * n + hi
        order = np.argsort(key, kind="stable")
        bad = np.zeros(len(e), dtype=bool)
        bad[order[1:]] = key[order[1:]] == key[order[:-1]]
        bad |= lo == hi
        idx = np.flatnonzero(bad)
        if not len(idx):
            return e
        other = rng.choice(np.flatnonzero(~bad), len(idx), replace=False)
        e[idx, 1], e[other, 1] = e[other, 1], e[idx, 1].copy()
    raise RuntimeError("could not rewire the knows graph to a simple graph")


def inherited_tags(rng, counts: np.ndarray, parent: np.ndarray, parent_tags,
                   n_tags: int, share: float) -> Tuple[np.ndarray, np.ndarray]:
    """(message, tag) pairs of messages that reply to ``parent``: each of a
    message's ``counts`` tags is, with probability ``share``, one of its
    parent's tags (``parent_tags`` = (owner, tag) sorted by owner), else
    a uniform tag; a message's tags are distinct."""
    owner, tag = picks(rng, counts, n_tags)
    p_owner, p_tag = parent_tags
    lo = np.searchsorted(p_owner, parent[owner], "left")
    n_par = np.searchsorted(p_owner, parent[owner], "right") - lo
    take = (n_par > 0) & (rng.random(len(owner)) < share)
    cand = tag.copy()
    cand[take] = p_tag[lo[take] + (rng.random(int(take.sum())) * n_par[take]).astype(np.int64)]
    # an inherited tag that repeats another of the message's tags is
    # drawn again, uniformly
    for _ in range(1000):
        key = owner.astype(np.int64) * n_tags + cand
        order = np.argsort(key, kind="stable")
        dup = np.zeros(len(key), dtype=bool)
        dup[order[1:]] = key[order[1:]] == key[order[:-1]]
        if not dup.any():
            return owner, cand
        cand[dup] = rng.integers(n_tags, size=int(dup.sum()))
    raise RuntimeError("could not draw distinct tags")


def generate(params: Dict[str, float], seed: int) -> Dataset:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 16]))
    P = {k: int(v) if float(v).is_integer() else float(v) for k, v in params.items()}
    t = TermTable()
    pred = {p: t.add(p) for p in PREDICATES}

    def nodes(cls: str, n: int) -> np.ndarray:
        return t.add_many([f"sn:{cls}{i}" for i in range(n)])

    classes = {c: t.add(f"sn:{c}") for c in (
        "Continent", "Country", "City", "TagClass", "Tag", "University", "Company",
        "Person", "Forum", "Post", "Comment", "Message")}
    continent = nodes("Continent", P["continents"])
    country = nodes("Country", P["countries"])
    city = nodes("City", P["cities"])
    tagclass = nodes("TagClass", P["tag_classes"])
    tag = nodes("Tag", P["tags"])
    university = nodes("University", P["universities"])
    company = nodes("Company", P["companies"])
    person = nodes("Person", P["persons"])
    forum = nodes("Forum", P["forums"])
    post = nodes("Post", P["posts"])
    comment = nodes("Comment", P["comments"])
    n_person, n_tag = len(person), len(tag)
    parts = []

    def edges(s, p: str, o) -> None:
        s, o = np.broadcast_arrays(np.asarray(s, np.int32), np.asarray(o, np.int32))
        parts.append(np.stack([s, np.full(len(s), pred[p], np.int32), o], axis=1))

    for cls, ids in (("Continent", continent), ("Country", country), ("City", city),
                     ("TagClass", tagclass), ("Tag", tag), ("University", university),
                     ("Company", company), ("Person", person), ("Forum", forum),
                     ("Post", post), ("Comment", comment), ("Message", post),
                     ("Message", comment)):
        edges(ids, "rdf:type", classes[cls])

    # places and tags, dealt evenly in an order the seed draws
    def even(n: int, of: int) -> np.ndarray:
        return rng.permutation(np.arange(n) % of)

    country_of_city = even(len(city), len(country))
    edges(city, "sn:isPartOf", country[country_of_city])
    edges(country, "sn:isPartOf", continent[even(len(country), len(continent))])
    edges(tagclass[1:], "sn:isSubclassOf",
          tagclass[(rng.random(len(tagclass) - 1) * np.arange(1, len(tagclass))).astype(int)])
    edges(tag, "sn:hasType", tagclass[even(len(tag), len(tagclass))])
    edges(university, "sn:isLocatedIn", city[rng.integers(len(city), size=len(university))])
    edges(company, "sn:isLocatedIn", country[rng.integers(len(country), size=len(company))])

    # persons: countries by a Zipf share of fixed size, a city of the country
    share = 1.0 / np.arange(1, len(country) + 1) ** P["country_skew"]
    per_country = np.floor(share / share.sum() * n_person).astype(np.int64)
    per_country[: n_person - per_country.sum()] += 1
    p_country = rng.permutation(len(country))[np.repeat(np.arange(len(country)), per_country)]
    p_country = p_country[rng.permutation(n_person)]
    cities_of = [np.flatnonzero(country_of_city == c) for c in range(len(country))]
    p_city = np.array([rng.choice(cities_of[c]) for c in p_country])
    edges(person, "sn:isLocatedIn", city[p_city])

    cap = max(1, n_person // 3)
    k = knows_edges(rng, dealt(rng, n_person, 2 * P["knows"], P["knows_sigma"], cap))
    edges(person[np.r_[k[:, 0], k[:, 1]]], "sn:knows", person[np.r_[k[:, 1], k[:, 0]]])
    o, v = picks(rng, dealt(rng, n_person, P["interests"], P["interest_sigma"], n_tag), n_tag)
    edges(person[o], "sn:hasInterest", tag[v])
    o, v = picks(rng, dealt(rng, n_person, P["study_at"], 0.0, 1), len(university))
    edges(person[o], "sn:studyAt", university[v])
    o, v = picks(rng, dealt(rng, n_person, P["work_at"], 0.0, 3), len(company))
    edges(person[o], "sn:workAt", company[v])

    # forums
    edges(forum, "sn:hasModerator", person[rng.integers(n_person, size=len(forum))])
    o, v = picks(rng, dealt(rng, len(forum), P["memberships"], P["member_sigma"], n_person),
                 n_person)
    edges(forum[o], "sn:hasMember", person[v])
    o, v = picks(rng, dealt(rng, len(forum), P["forum_tags"], P["tag_sigma"], n_tag), n_tag)
    edges(forum[o], "sn:hasTag", tag[v])
    post_forum = np.repeat(np.arange(len(forum)),
                           dealt(rng, len(forum), len(post), P["post_sigma"], len(post)))
    edges(forum[post_forum], "sn:containerOf", post)

    # messages: posts, then comments on posts, then comments on comments
    n_post, n_comment = len(post), len(comment)
    n_first = int(round(n_comment * P["comments_on_posts"]))
    replies = dealt(rng, n_post, n_first, P["reply_sigma"], n_first)
    parent_post = rng.permutation(np.repeat(np.arange(n_post), replies))
    replies = dealt(rng, n_first, n_comment - n_first, P["reply_sigma"], n_comment)
    parent_comment = rng.permutation(np.repeat(np.arange(n_first), replies))
    edges(comment[:n_first], "sn:replyOf", post[parent_post])
    edges(comment[n_first:], "sn:replyOf", comment[parent_comment])

    creator = rng.integers(n_person, size=n_post + n_comment)
    message = np.r_[post, comment]
    edges(message, "sn:hasCreator", person[creator])
    edges(message, "sn:isLocatedIn", country[p_country[creator]])

    post_tags = picks(rng, dealt(rng, n_post, P["post_tags"], P["tag_sigma"], n_tag), n_tag)
    tag_counts = dealt(rng, n_comment, P["comment_tags"], P["tag_sigma"], n_tag)
    first_tags = inherited_tags(rng, tag_counts[:n_first], parent_post, post_tags, n_tag,
                                P["parent_tag_share"])
    rest_tags = inherited_tags(rng, tag_counts[n_first:], parent_comment, first_tags, n_tag,
                               P["parent_tag_share"])
    edges(post[post_tags[0]], "sn:hasTag", tag[post_tags[1]])
    edges(comment[first_tags[0]], "sn:hasTag", tag[first_tags[1]])
    edges(comment[n_first + rest_tags[0]], "sn:hasTag", tag[rest_tags[1]])

    o, v = picks(rng, dealt(rng, n_post, P["post_likes"], P["like_sigma"], n_person), n_person)
    edges(person[v], "sn:likes", post[o])
    o, v = picks(rng, dealt(rng, n_comment, P["comment_likes"], P["like_sigma"], n_person),
                 n_person)
    edges(person[v], "sn:likes", comment[o])

    sizes = {k: P[k] for k in ("persons", "forums", "posts", "comments", "tags", "knows")}
    return Dataset.from_parts(t, parts, {}, sizes)
