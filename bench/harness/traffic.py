"""The one traffic generator: it reads a mix file (``bench/traffic/*.json``)
and a query-set file (``bench/queries/*.json``) and turns them, with a
seed and the benchmark's copy of the store, into requests.

A mix names its query set, the queries of one query mix in the order a
client sends them (a query listed k times weighs k), how many whole mixes
warm-up sends, constants that stay ``fixed`` for the run (written into
the text as they are), and for each placeholder of a query the ``pool``
of the store's entities whose member it binds: a uniform draw for each
request, as the BSBM driver makes them. Warm-up and window draw from
streams of their own.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Iterator, List, Union

import numpy as np

from bench.harness.dataset import Dataset

WINDOW_STREAM, WARMUP_STREAM = 1, 2


@dataclasses.dataclass(frozen=True)
class Request:
    key: str  # "<query>.<k>": the query, and the number of the mix it belongs to
    query: str
    text: str
    bind: Dict[str, Union[int, float]]  # placeholder -> term code, or a fixed constant


class Traffic:
    def __init__(self, mix: dict, queries: Dict[str, dict], ds: Dataset, seed: int):
        self.mix = mix
        self.queries = queries
        self.ds = ds
        self.seed = seed

    def rng(self, stream: int) -> np.random.Generator:
        return np.random.default_rng(np.random.SeedSequence([self.seed, stream]))

    def _request(self, query: str, k: int, rng) -> Request:
        text = self.queries[query]["text"]
        bind: Dict[str, Union[int, float]] = {}
        for d in self.mix["bind"].get(query, ()):
            pool = self.ds.pools[d["pool"]]
            bind[d["as"]] = code = int(pool[rng.integers(len(pool))])
            text = text.replace(d["as"], self.ds.text(code))
        for placeholder, value in self.mix.get("fixed", {}).items():
            if placeholder in text:
                bind[placeholder] = value
                text = text.replace(placeholder, repr(value))
        return Request(f"{query}.{k}", query, text, bind)

    def _mixes(self, stream: int) -> Iterator[Request]:
        rng = self.rng(stream)
        for k in itertools.count():
            for q in self.mix["order"]:
                yield self._request(q, k, rng)

    def warmup(self) -> List[Request]:
        n = int(self.mix["warmup_mixes"]) * len(self.mix["order"])
        return list(itertools.islice(self._mixes(WARMUP_STREAM), n))

    def window(self) -> Iterator[Request]:
        return self._mixes(WINDOW_STREAM)
