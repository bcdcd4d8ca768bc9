"""The ``lsqb-sf0.1`` configuration and its cell: the cut scales persons
and what grows with them alike; the join-row readers on hand-built
traces; and the ``float32_sums`` control, the reference with its counts
summed in float32, which the cell's own compare finds wrong at the
cell's size (a count passes 2^24 there) and right on the tiny cut."""

import itertools
import json

import numpy as np
import pytest

from bench.harness import check
from bench.harness.runner import Cell, load_module
from bench.harness.traffic import Traffic
from bench.tests.test_bench_phase_readers import run
from bench.tests.tiny_bench import REPO, TINY
from repro.core import telemetry

CELL = "lsqb-sf0.1.counts"


def test_lsqb_config_cuts_persons_and_what_scales_with_them_alike():
    cfg = json.loads((REPO / "bench" / "configs" / "lsqb-sf0.1.json").read_text())
    src, params = cfg["source_counts"], cfg["params"]
    changed = {k for k in src if k in params and params[k] != src[k]}
    assert changed == set(cfg["reduced"]) and "persons" in changed
    factor = params["persons"] / src["persons"]
    for k in changed:
        assert params[k] / src[k] == pytest.approx(factor, rel=0.01)
    # the dictionaries Datagen draws from do not scale with the persons
    for k in ("tags", "tag_classes", "countries", "cities"):
        assert params[k] == src[k]
    assert set(TINY["lsqb-sf0.1"]) <= set(params)


def joined(t0, rows, weight):
    tr = telemetry.QueryTrace("j")
    tr.t0, tr.join_rows, tr.join_weight = t0, rows, weight
    return tr


def count_reader(metric):
    return Cell(REPO, CELL).reader(metric)


@pytest.mark.parametrize("metric,want", [
    ("join_rows", (400 + 100) / 2),
    ("rows_per_join_row", (5000 + 100) / (400 + 100)),  # the warm-up trace is not read
])
def test_join_readers(monkeypatch, metric, want):
    kept = [joined(-5.0, 10**6, 10**9), joined(0.1, 400, 5000), joined(1.1, 100, 100)]
    monkeypatch.setattr(telemetry, "recent_traces", lambda: kept)
    assert count_reader(metric)(run()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["join_rows", "rows_per_join_row"])
def test_join_readers_find_nothing_in_a_program_without_the_counters(monkeypatch, metric):
    class OldTrace:  # a request trace of a program that counts no join rows
        def __init__(self, t0):
            self.t0 = t0

    monkeypatch.setattr(telemetry, "recent_traces", lambda: [OldTrace(0.1), OldTrace(1.1)])
    assert count_reader(metric)(run()) is None
    monkeypatch.delattr(telemetry, "recent_traces")
    assert count_reader(metric)(run()) is None


def test_rows_per_join_row_is_absent_where_no_join_emitted(monkeypatch):
    monkeypatch.setattr(telemetry, "recent_traces",
                        lambda: [joined(0.1, 0, 0), joined(1.1, 0, 0)])
    assert count_reader("rows_per_join_row")(run()) is None
    assert count_reader("join_rows")(run()) == 0


def float32_readings(params, seed):
    """The float32_sums control judged as a run is, and the answers."""
    cell = Cell(REPO, CELL)
    ds = cell.generator.generate(params, seed)
    requests = list(itertools.islice(Traffic(cell.mix, cell.queries, ds, seed).window(), 9))
    reference = load_module(cell.reference_path).Reference(ds)
    answers = check.control_answers({"kind": "float32_sums"}, reference, cell.queries, requests)
    correct, numbers = check.judge(answers, reference, cell.queries,
                                   [r.bind for r in requests], 0, cell.mix["limits"])
    return correct, numbers, dict(answers), reference


def test_float32_sums_are_exact_below_2_to_the_24():
    cell = Cell(REPO, CELL)
    correct, numbers, _, _ = float32_readings(
        dict(cell.config["params"], **TINY["lsqb-sf0.1"]), 5)
    assert correct and numbers["wrong_answers"]["value"] == 0


def test_the_cell_finds_counts_summed_in_float32_wrong_at_its_size():
    """At the cell's own size q6 counts more than 2^24: summed in float32
    it comes out wrong, by the limit on wrong answers and not on errors."""
    cell = Cell(REPO, CELL)
    correct, numbers, answers, reference = float32_readings(cell.config["params"], 2**31 + 7)
    assert reference.q6() > 2**24
    assert not correct
    assert numbers["wrong_answers"]["value"] >= 1 and numbers["errors"]["value"] == 0
    assert answers["q6"] != reference.answer("q6", {})
    assert answers["q3"] == reference.answer("q3", {})  # a small count stays exact


def test_the_reference_sums_counts_in_the_dtype_it_is_given():
    cell = Cell(REPO, CELL)
    ds = cell.generator.generate(dict(cell.config["params"], **TINY["lsqb-sf0.1"]), 5)
    ref = load_module(cell.reference_path).Reference(ds)
    assert ref.total(np.full(3, 2**24, dtype=np.int64) + 1) == 3 * 2**24 + 3
    low = type(ref)(ds, acc=np.float32)
    # 2^24 + 1 is not a float32: each term rounds, and so does the sum
    assert low.total(np.full(3, 2**24, dtype=np.int64) + 1) != 3 * 2**24 + 3
    assert low.total(np.ones(2**24 + 8, dtype=bool)) == 2**24
