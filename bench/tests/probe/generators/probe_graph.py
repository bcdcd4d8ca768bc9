"""A small random social graph for the harness's own tests: persons who
know each other (each pair both ways, as an undirected edge) and tags
they are interested in. Each person draws ``knows_per_person`` others
and ``interests_per_person`` tags, uniformly and without repeats, from
the seed; no entity is ever a query constant, so the graph has no pools."""

from __future__ import annotations

from typing import Dict

import numpy as np

from bench.harness.dataset import Dataset, TermTable


def _draws(rng, n: int, k: int, of: int, skip_self: bool) -> np.ndarray:
    """(owner, pick) pairs: each of ``n`` owners picks ``k`` distinct
    members of ``range(of)``, itself left out where ``skip_self``."""
    picks = [rng.choice(np.setdiff1d(np.arange(of), [i]) if skip_self else of, k, replace=False)
             for i in range(n)]
    return np.stack([np.repeat(np.arange(n), k), np.concatenate(picks)], axis=1)


def generate(params: Dict[str, int], seed: int) -> Dataset:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    n_person, n_tag = params["persons"], params["tags"]
    t = TermTable()
    knows, interest = t.add("probe:knows"), t.add("probe:hasInterest")
    persons = t.add_many([f"probe:Person{i}" for i in range(n_person)])
    tags = t.add_many([f"probe:Tag{i}" for i in range(n_tag)])

    edges = _draws(rng, n_person, params["knows_per_person"], n_person, True)
    edges = np.r_[edges, edges[:, ::-1]]
    likes = _draws(rng, n_person, params["interests_per_person"], n_tag, False)

    def triples(s, pred, o):
        return np.stack([s, np.full(len(s), pred, np.int32), o], axis=1)

    parts = [triples(persons[edges[:, 0]], knows, persons[edges[:, 1]]),
             triples(persons[likes[:, 0]], interest, tags[likes[:, 1]])]
    sizes = {"persons": n_person, "tags": n_tag}
    return Dataset.from_parts(t, parts, {}, sizes)
