"""SIP key seeks on the benchmark's own store: a q10 request on a tiny
generated BSBM store reads a tenth of the rows or less that the range +
bloom path reads, with the same answer; and the readers of the two SIP
counters (``sip_scan_rows``, ``sip_seeks``) on a synthetic traced run."""

import itertools

import numpy as np
import pytest

from bench.harness.record import Done, Run
from bench.harness.runner import Cell
from bench.harness.traffic import Traffic
from bench.tests.tiny_bench import REPO, SEED, TINY
from repro.core import Engine, telemetry
from repro.core.operators import scan as scan_mod
from repro.kernels import ops

CELL = "bsbm-25m.explore"


@pytest.fixture(scope="module")
def tiny():
    cell = Cell(REPO, CELL)
    params = dict(cell.config["params"], **TINY[cell.workload["config"]])
    ds = cell.generator.generate(params, SEED)
    return cell, ds, ds.load()


def rows_scanned(op):
    return op.stats.rows_scanned + sum(rows_scanned(c) for c in op.children())


def test_q10_reads_a_tenth_of_the_rows_of_range_and_bloom(tiny, monkeypatch):
    cell, ds, store = tiny
    traffic = Traffic(cell.mix, cell.queries, ds, SEED)
    requests = list(itertools.islice((r for r in traffic.window() if r.query == "q10"), 2))
    with ops.data_plane("numpy"):
        seek = [Engine(store).execute(r.text) for r in requests]
        monkeypatch.setattr(scan_mod, "SEEK_ROWS_PER_KEY", 1 << 40)  # range + bloom
        ranged = [Engine(store).execute(r.text) for r in requests]
    for a, b in zip(seek, ranged):
        np.testing.assert_array_equal(a.rows, b.rows)
        assert a.trace.sip_seeks > 0 and b.trace.sip_seeks == 0
        assert rows_scanned(a.root) * 10 < rows_scanned(b.root)
        assert a.trace.sip_scan_rows * 10 < b.trace.sip_scan_rows


def counted(t0, rows, seeks):
    tr = telemetry.QueryTrace("q")
    tr.t0 = t0
    tr.sip_scan_rows, tr.sip_seeks = rows, seeks
    return tr


def run():
    reqs = [Done("a.0", "a", 0.0, 1.0, [], 0, 1), Done("b.0", "b", 1.0, 2.0, [], 0, 1)]
    return Run(reqs, (0.0, 2.0), 1.0, [])


@pytest.mark.parametrize("metric,want", [("sip_scan_rows", (120 + 30) / 2),
                                         ("sip_seeks", (3 + 0) / 2)])
def test_sip_readers(monkeypatch, metric, want):
    kept = [counted(-5.0, 10**6, 9), counted(0.1, 120, 3), counted(1.1, 30, 0)]
    monkeypatch.setattr(telemetry, "recent_traces", lambda: kept)
    assert Cell(REPO, CELL).reader(metric)(run()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["sip_scan_rows", "sip_seeks"])
def test_sip_readers_find_nothing_in_a_program_without_the_counters(monkeypatch, metric):
    class OldTrace:  # a request trace of a program that keeps no SIP counters
        def __init__(self, t0):
            self.t0 = t0

    monkeypatch.setattr(telemetry, "recent_traces", lambda: [OldTrace(0.1), OldTrace(1.1)])
    assert Cell(REPO, CELL).reader(metric)(run()) is None
    monkeypatch.delattr(telemetry, "recent_traces")
    assert Cell(REPO, CELL).reader(metric)(run()) is None
