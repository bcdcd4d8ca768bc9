"""What decides ``correct`` fails what it must. The control (the plain
reference put in the program's place with one guarantee broken) comes
out not correct in every cell and in the probe, and so does a whole run
with the timed path broken underneath: an answer left unchanged from the
request before, half of each emitted batch left out, and an answer
altered where the server produces it. (One chip: no exchange between
chips to leave out.) The reference in the program's place comes out
correct."""

import itertools

import numpy as np
import pytest

from bench import control
from bench.harness import check
from bench.harness.runner import Cell, load_module
from bench.harness.traffic import Traffic
from bench.tests.tiny_bench import PER_CELL, SEED, off_chip, probe_root, run_cell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return probe_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload", PER_CELL)
def test_control_is_not_correct(root, workload):
    got = control.readings(root, workload, SEED)
    assert got["correct"] is False
    assert got["check"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("workload", PER_CELL)
def test_reference_in_the_programs_place_is_correct(root, workload):
    cell = Cell(root, workload)
    ds = cell.generator.generate(cell.config["params"], SEED)
    requests = list(itertools.islice(Traffic(cell.mix, cell.queries, ds, SEED).window(), 30))
    module = load_module(cell.reference_path)
    ref = module.Reference(ds)
    answers = [(r.query, check.solution(ref.answer(r.query, r.bind), cell.queries[r.query]))
               for r in requests]
    correct, numbers = check.judge(answers, module.Reference(ds), cell.queries,
                                   [r.bind for r in requests], 0, cell.mix["limits"])
    assert correct and all(n["value"] == 0 for n in numbers.values())


def stale(monkeypatch):
    from repro.serve.query_server import QueryServer

    execute, last = QueryServer.execute, []

    def unchanged(self, key, text):
        result = execute(self, key, text)
        last.append(result)
        return last[-2] if len(last) > 1 else result

    monkeypatch.setattr(QueryServer, "execute", unchanged)


def half_batch(monkeypatch):
    from repro.kernels import ops

    gather_emit = ops.gather_emit

    def half(*args, **kwargs):
        block, mask = gather_emit(*args, **kwargs)
        mask = np.array(mask, dtype=bool)
        mask[len(mask) // 2:] = False
        return block, mask

    monkeypatch.setattr(ops, "gather_emit", half)


def altered(monkeypatch):
    from repro.serve.query_server import QueryServer

    execute = QueryServer.execute

    def alter(self, key, text):
        result = execute(self, key, text)
        if result.rows is not None and len(result.rows):
            rows = np.array(result.rows)
            rows[0, -1] = (rows[0, -1] + 1) % len(self.store.dict)
            result.rows = rows
        return result

    monkeypatch.setattr(QueryServer, "execute", alter)


@pytest.mark.parametrize("fault", [stale, half_batch, altered], ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", PER_CELL)
def test_a_run_with_a_broken_timed_path_is_not_correct(root, workload, fault, monkeypatch,
                                                       capsys):
    with off_chip(monkeypatch):
        fault(monkeypatch)
        result = run_cell(root, workload, capsys)
    assert result["attempted"] > 0
    assert result["correct"] is False
    assert result["check"]["wrong_answers"]["value"] > 0
