"""Each cell's store and traffic are made from the seed alone: the same
seed gives the same triples and requests, another seed another store
and, where the mix binds constants, other requests, and seeds larger
than 32 bits work. Requests follow the mix's order, draw each
constant uniformly from its pool, and the store keeps BSBM's counts per
class and per product."""

import itertools
import json

import numpy as np
import pytest

from bench.harness.runner import Cell
from bench.harness.traffic import Traffic
from bench.tests.tiny_bench import PER_CELL, REPO, SEED, TINY, probe_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return probe_root(tmp_path_factory.mktemp("tiny"))


def traffic(root, workload, seed, n=40):
    cell = Cell(root, workload)
    ds = cell.generator.generate(cell.config["params"], seed)
    t = Traffic(cell.mix, cell.queries, ds, seed)
    window = list(itertools.islice(t.window(), n))
    return ds, t.warmup(), window


@pytest.mark.parametrize("workload", PER_CELL)
def test_same_seed_same_store_and_requests(root, workload):
    ds1, warm1, win1 = traffic(root, workload, SEED)
    ds2, warm2, win2 = traffic(root, workload, SEED)
    assert np.array_equal(ds1.spo, ds2.spo) and ds1.terms == ds2.terms
    assert [r.text for r in warm1] == [r.text for r in warm2]
    assert [r.text for r in win1] == [r.text for r in win2]


@pytest.mark.parametrize("workload", PER_CELL)
def test_another_seed_another_store(root, workload):
    ds1, _, win1 = traffic(root, workload, SEED)
    ds2, _, win2 = traffic(root, workload, SEED + 1)
    assert not np.array_equal(ds1.spo, ds2.spo)
    # the seed draws constants only where the mix binds some
    texts_differ = [r.text for r in win1] != [r.text for r in win2]
    assert texts_differ == bool(Cell(root, workload).mix["bind"])


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**32 + 5, 2**40 + 3])
def test_large_seeds(root, seed):
    ds, warm, win = traffic(root, "bsbm-25m.explore", seed, n=10)
    assert len(ds.spo) > 0 and len(win) == 10 and warm


def test_cyclic_order_and_keys(root):
    cell = Cell(root, "bsbm-25m.explore")
    order = cell.mix["order"]
    _, warm, win = traffic(root, "bsbm-25m.explore", SEED, n=2 * len(order))
    assert [r.query for r in win] == order * 2
    assert [r.key for r in win[:2]] == [f"{order[0]}.0", f"{order[1]}.0"]
    assert win[len(order)].key == f"{order[0]}.1"
    assert [r.query for r in warm] == order * cell.mix["warmup_mixes"]
    # warm-up draws from a stream of its own
    assert [r.text for r in warm[:len(order)]] != [r.text for r in win[:len(order)]]


def test_constants_are_uniform_draws_from_their_pools(root):
    cell = Cell(root, "bsbm-25m.explore")
    ds, _, win = traffic(root, "bsbm-25m.explore", SEED, n=2000)
    for r in win:
        for d in cell.mix["bind"][r.query]:
            assert r.bind[d["as"]] in set(ds.pools[d["pool"]].tolist())
            assert ds.text(r.bind[d["as"]]) in r.text and d["as"] not in r.text
    products = [r.bind["%PRODUCT%"] for r in win if "%PRODUCT%" in r.bind]
    # about as many distinct products as uniform draws with repeats give
    n, k = len(ds.pools["product"]), len(products)
    assert abs(len(set(products)) - n * (1 - (1 - 1 / n) ** k)) < 0.1 * n


def test_fixed_constants_are_written_into_the_text(root):
    _, _, win = traffic(root, "bsbm-25m.explore", SEED, n=20)
    for r in win:
        if r.query in ("q7", "q10"):
            assert r.bind["%CURRENT_DATE%"] == 20090701 and "20090701" in r.text
        assert "%" not in r.text


def test_every_product_has_bsbm_records(root):
    cell = Cell(root, "bsbm-25m.explore")
    params = cell.config["params"]
    ds, _, _ = traffic(root, "bsbm-25m.explore", SEED, n=1)
    preds = ds.spo[:, 1]
    count = lambda p: int((preds == ds.pred(p)).sum())  # noqa: E731
    n = params["products"]
    assert count("bsbm:product") == n * params["offers_per_product"]
    assert count("bsbm:reviewFor") == n * params["reviews_per_product"]
    for p in ("rdfs:comment", "bsbm:producer", "bsbm:productPropertyNumeric1",
              "bsbm:productPropertyTextual3"):
        assert count(p) >= n
    per_product = count("bsbm:productFeature") / n
    assert 15 <= per_product <= 39
    # BSBM's about 350 triples a product
    assert 300 <= len(ds.spo) / n <= 400


def test_every_seed_makes_a_store_of_the_same_sizes(root):
    """The seed draws the order, not the sizes, of what the queries join
    on: offers and reviews, each country's vendors, and a product's offers
    from each country count the same on every seed, and a product's offers
    from one country lie as far apart in their numbering on every product."""

    def sizes(seed):
        ds, _, _ = traffic(root, "bsbm-25m.explore", seed, n=1)
        spo, code = ds.spo, ds.pred
        preds = [int((spo[:, 1] == code(p)).sum()) for p in (
            "bsbm:product", "bsbm:vendor", "bsbm:reviewFor", "rev:reviewer", "bsbm:country")]
        of = lambda p: dict(spo[spo[:, 1] == code(p)][:, [0, 2]].tolist())  # noqa: E731
        country, vendor, product = of("bsbm:country"), of("bsbm:vendor"), of("bsbm:product")
        number = {c: int(ds.terms[c].rsplit("Offer", 1)[1]) for c in product}
        by = {}
        for offer, p in product.items():
            by.setdefault((p, country[vendor[offer]]), []).append(number[offer])
        shares = sorted(len(v) for v in by.values())
        gaps = {max(v) - min(v) for v in by.values()}
        vendors = sorted(np.unique([country[v] for v in set(vendor.values())],
                                   return_counts=True)[1].tolist())
        n_product = len(set(product.values()))
        return preds, shares, vendors, gaps, n_product

    a, b = sizes(SEED), sizes(SEED + 1)
    assert a[:3] == b[:3]
    assert len(set(a[1])) == 1  # every product has as many offers from each country
    for gaps, n_product in ((a[3], a[4]), (b[3], b[4])):
        # a product's first and last offer of a country lie the same number
        # of rounds apart, give or take less than one round
        rounds = round(sum(gaps) / len(gaps) / n_product)
        assert rounds > 0 and all(abs(g - rounds * n_product) < n_product for g in gaps)


def test_config_files_state_the_source_sizes():
    cfg = json.loads((REPO / "bench" / "configs" / "bsbm-25m.json").read_text())
    src, params = cfg["source_counts"], cfg["params"]
    assert src["products"] == 70812
    assert src["offers"] == 20 * src["products"] and src["reviews"] == 10 * src["products"]
    assert params["offers_per_product"] == 20 and params["reviews_per_product"] == 10
    derived = dict(params, offers=params["products"] * params["offers_per_product"],
                   reviews=params["products"] * params["reviews_per_product"])
    changed = {k for k in src if k != "triples" and derived[k] != src[k]}
    assert changed | {"triples"} == set(cfg["reduced"])
    # every class is cut by the same factor, so products per type, feature,
    # producer and vendor, and reviews per reviewer, stay
    for k in changed:
        assert derived[k] / src[k] == pytest.approx(0.25, rel=0.01)
    assert set(TINY["bsbm-25m"]) <= set(params)
