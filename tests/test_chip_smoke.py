"""chip_smoke.py on the CPU: its workload on the Pallas plane (interpret
mode) answers exactly as the numpy plane, and the script itself refuses
to run without a TPU."""

from __future__ import annotations

import importlib.util
import pathlib

import pytest

from repro.data import generate_ecommerce_graph

_SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_workload_pallas_matches_numpy(smoke):
    store, meta = generate_ecommerce_graph(scale=0.05, seed=3)
    requests = smoke.workload(store, meta, seed=3, instances=1)
    assert [k for k, _ in requests] == [
        "e1.0", "e2.0", "e3.0", "e4.0", "e5.0", "b1", "b3", "b5", "b8"
    ]
    ref = smoke.Pass(store, requests, backend="numpy")
    run = smoke.Pass(store, requests, backend="pallas")
    assert smoke.mismatches(ref, run) == []
    assert ref.n_rows["e1.0"] > 0
    assert sum(ref.n_rows.values()) > 0
    assert {b for (_, b) in ref.ledger.backend_counts} == {"numpy"}
    backends = {b for (_, b) in run.ledger.backend_counts}
    assert backends == {"pallas"}
    kernels = {k for (k, _) in run.ledger.backend_counts}
    assert {"hash_probe", "join_expand", "gather_emit",
            "segment_reduce"} <= kernels


def test_same_answer_float_tolerance(smoke):
    assert smoke.same_answer([(1, 2.0)], [(1, 2.0 * (1 + 5e-6))])
    assert not smoke.same_answer([(1, 2.0)], [(1, 2.0 * (1 + 5e-5))])
    assert not smoke.same_answer([(1, 2.0)], [(1, 2)])  # int vs float term
    assert not smoke.same_answer([(1,)], [(1,), (1,)])


def test_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""
