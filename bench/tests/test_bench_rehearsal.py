"""Each cell's whole run on the CPU at a tiny scale, kernels in the TPU
interpreter: store, warm-up, a five-second closed loop, the check and the
result line, untraced and traced. Only the look for a chip and the
compile cache are skipped; no number here is a device's."""

import pytest

from bench.harness.runner import Cell
from bench.tests.tiny_bench import PER_CELL, off_chip, probe_root, run_cell

DEVICE_READ = ("device_ms", "dispatch_host_ms", "idle_pct", "roofline_pct")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return probe_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", PER_CELL)
def test_cell_runs_end_to_end_on_interpreted_kernels(root, workload, trace, monkeypatch,
                                                     capsys):
    # a window long enough for an interpreted request to complete on a
    # loaded machine
    with off_chip(monkeypatch):
        result = run_cell(root, workload, capsys, seconds=5.0, trace=trace, plane="pallas")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "check"
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    want = {m["name"] for m in Cell(root, workload).metrics(traced=bool(trace))}
    if trace:
        # no device in the trace: its readers find nothing, and say so
        want = {m for m in want if m.split(".")[0] not in DEVICE_READ}
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert {"busy_s", "window_s"} <= set(result["device"])
    # mixes per hour need a request of every query of the mix, which an
    # interpreted window this short may not complete
    assert want - {"qmph"} <= set(result["metrics"]) <= want
    for m in result["metrics"].values():
        assert m["value"] >= 0 and m["unit"]
