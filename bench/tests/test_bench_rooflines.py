"""Each ``bench/rooflines/`` function counts the logical work of a kernel
call, the unpadded inputs and outputs as the operator hands them over:
the same (ops, bytes) for one call on the numpy plane, on the Pallas
plane, and on the Pallas plane padded to larger buckets."""

import json

import numpy as np
import pytest

from bench.harness import devtrace
from bench.harness.runner import Cell
from bench.tests.tiny_bench import CELLS, REPO, TINY


def serve_and_cost(store, texts, plane):
    from repro.kernels import ops
    from repro.serve.query_server import QueryServer

    server = QueryServer(store)
    calls = devtrace.KernelCalls(ops, Cell(REPO, CELLS[0]).costs(devtrace.kernels(ops)))
    with ops.data_plane(plane), calls:
        for i, text in enumerate(texts):
            server.execute(f"r{i}", text)
    assert not calls.cost_errors
    return [(c.kernel, c.cost) for c in calls.calls]


@pytest.fixture(scope="module")
def stores():
    from bench.generators import bsbm

    cfg = json.loads((REPO / "bench" / "configs" / "bsbm-25m.json").read_text())
    ds = bsbm.generate(dict(cfg["params"], **TINY["bsbm-25m"]), 5)
    return {"bsbm-25m": (ds, ds.load())}


QUERIES = {
    # hash joins with SIP bloom filters, emission, OPTIONAL, a FILTER
    # through the expression VM, ORDER BY with LIMIT
    "bsbm-25m": ["q7", "q8"],
}


@pytest.mark.parametrize("config", sorted(QUERIES))
def test_costs_do_not_see_the_padding(config, stores, monkeypatch):
    from repro.kernels import tiling

    ds, store = stores[config]
    queries = json.loads((REPO / "bench" / "queries" / "bsbm.json").read_text())
    product = ds.text(int(ds.pools["product"][3]))
    texts = [queries[q]["text"].replace("%PRODUCT%", product).replace(
        "%CURRENT_DATE%", "20090701") for q in QUERIES[config]]
    numpy_plane = serve_and_cost(store, texts, "numpy")
    pallas_plane = serve_and_cost(store, texts, "pallas")
    bucket = tiling.bucket
    monkeypatch.setattr(tiling, "bucket", lambda n, tile: bucket(n, 4 * tile))
    wider = serve_and_cost(store, texts, "pallas")
    kernels = {k for k, _ in numpy_plane}
    assert {"hash_probe", "gather_emit", "bloom_probe", "expr_eval"} <= kernels
    assert all(cost is not None and cost[0] > 0 and cost[1] > 0 for _, cost in numpy_plane)
    assert numpy_plane == pallas_plane == wider


def direct_calls():
    """Calls of the kernels the cells' queries do not reach."""
    rng = np.random.default_rng(0)
    keys = np.sort(rng.integers(0, 1000, 300)).astype(np.int32)
    queries = rng.integers(0, 1000, 77).astype(np.int32)
    vis_hi = np.zeros(50, np.int32)
    vis_lo = np.sort(rng.choice(1000, 50, replace=False)).astype(np.int32)
    cand_lo = np.sort(rng.integers(0, 1000, 90)).astype(np.int32)
    return [
        ("sorted_search", (keys, queries, "left")),
        ("frontier_dedup", (np.zeros(90, np.int32), cand_lo, vis_hi, vis_lo)),
        ("segment_reduce", (keys, rng.integers(0, 50, 300).astype(np.float32), "sum")),
    ]


@pytest.mark.parametrize("kernel,args", direct_calls(),
                         ids=lambda x: x if isinstance(x, str) else "")
def test_direct_kernel_costs_do_not_see_the_padding(kernel, args, monkeypatch):
    from repro.kernels import ops, tiling

    cost = Cell(REPO, CELLS[0]).costs([kernel])[kernel]
    got = []
    for plane, scale in (("numpy", 1), ("pallas", 1), ("pallas", 8)):
        bucket = tiling.bucket
        monkeypatch.setattr(tiling, "bucket", lambda n, tile: bucket(n, scale * tile))
        result = getattr(ops, kernel)(*args, backend=plane)
        got.append(cost(result, *args, backend=plane))
        monkeypatch.undo()
    assert got[0] == got[1] == got[2]
    assert got[0][0] > 0 and got[0][1] > 0


def test_cost_grows_with_the_logical_size_inside_one_bucket():
    cost = Cell(REPO, CELLS[0]).costs(["segment_reduce"])["segment_reduce"]
    small = cost(None, np.arange(1100), np.ones(1100), "sum")
    large = cost(None, np.arange(2000), np.ones(2000), "sum")  # one 2048 bucket
    assert large[0] / small[0] == pytest.approx(2000 / 1100)
    assert large[1] / small[1] == pytest.approx(2000 / 1100)
