"""``BENCHMARK.json`` against the rules a manifest keeps: names and units
within their characters, the keys each entry may have, every file found
by name, every per-layer metric reported by each cell it lists, and a
cell, configuration, traffic mix and metric added by files and entries
alone, and so a configuration whose mix binds no constants (the probe)."""

import json
import re

import pytest

from bench.harness.runner import Cell
from bench.tests.tiny_bench import (CELLS, MANIFEST, PER_CELL, PROBE, REPO, SEED, add_probe,
                                     copy_source, make_root, off_chip, probe_root, run_cell,
                                     tiny_cuts)

MANIFESTS = pytest.mark.parametrize("manifest", [MANIFEST], ids=["committed"])
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return probe_root(tmp_path_factory.mktemp("tiny"))


def line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert all(line(w) for w in MANIFEST["command"])
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) <= 64 * 1024


def test_a_full_check_fits_its_time_with_24_cells():
    seconds = (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert seconds <= 43200


@MANIFESTS
def test_configs(manifest):
    assert 1 <= len(manifest["configs"]) <= 24
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        cfg = json.loads((REPO / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["guarantees"]


@MANIFESTS
def test_workloads(manifest):
    assert 1 <= len(manifest["workloads"]) <= 24
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(
        1, len(manifest["workloads"]) // 2)
    names = [w["name"] for w in manifest["workloads"]]
    assert len(set(names)) == len(names)


@MANIFESTS
def test_metrics(manifest):
    e2e, per_layer = manifest["end_to_end"], manifest["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per_layer) <= 128
    names = [m["name"] for m in e2e + per_layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in manifest["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in per_layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
    for m in e2e + per_layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", ())) <= cells
    assert [m["name"] for m in e2e if m["name"] == "setup_s"] == ["setup_s"]
    assert "workloads" not in next(m for m in e2e if m["name"] == "setup_s")


def test_roofline_shares_are_percentages():
    for m in MANIFEST["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"


@pytest.mark.parametrize("workload", PER_CELL)
def test_each_cell_reports_setup_another_end_to_end_and_a_per_layer_metric(root, workload):
    cell = Cell(root, workload)
    e2e = [m["name"] for m in cell.metrics(traced=False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.metrics(traced=True)


def test_every_per_layer_metric_moves_an_end_to_end_metric_each_of_its_cells_reports():
    for m in MANIFEST["per_layer"]:
        for w in m["workloads"]:
            reported = [e["name"] for e in Cell(REPO, w).metrics(traced=False)]
            assert m["moves"] in reported, (m["name"], w)


def test_metrics_of_one_layer_name_it_alike():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert layers == {"client", "server and planner", "batch operators", "kernel dispatch",
                      "kernels", "device"}


@pytest.mark.parametrize("workload", PER_CELL)
def test_every_file_of_a_cell_is_found_by_name(root, workload):
    cell = Cell(root, workload)
    assert cell.reference_path.exists()
    for m in cell.metrics(False) + cell.metrics(True):
        assert callable(cell.reader(m["name"]))
    for q in cell.mix["order"]:
        assert q in cell.queries
    for q in cell.mix["bind"]:
        assert q in cell.queries
    assert cell.mix["limits"] and cell.mix["control"]


def test_every_ledgered_kernel_has_a_roofline_reader():
    from bench.harness import devtrace
    from repro.kernels import ops

    kernels = devtrace.kernels(ops)
    assert len(kernels) >= 10
    assert set(Cell(REPO, CELLS[0]).costs(kernels)) == set(kernels)


def test_a_cell_configuration_mix_and_metric_are_added_by_files_alone(tmp_path, monkeypatch,
                                                                      capsys):
    """A throwaway cell on a new configuration and mix, reporting a new
    per-layer metric: new files and new manifest entries, no file of the
    benchmark edited."""
    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "bsbm-25m.json").read_text())
    cfg.update(name="bsbm-alt", params=dict(cfg["params"], products=200, reviewers=100))
    (bench / "configs" / "bsbm-alt.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "explore.json").read_text())
    mix.update(order=["q9", "q11", "q12"])
    (bench / "traffic" / "lookups.json").write_text(json.dumps(mix))
    (bench / "metrics" / "rows_per_request.py").write_text(
        "def read(run):\n"
        "    return sum(r.rows for r in run.requests) / len(run.requests)\n")
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "bsbm-alt", "source": "x", "why": "x", "reduced": [],
                                "file": "bench/configs/bsbm-alt.json"})
    manifest["workloads"].append({"name": "bsbm-alt.lookups", "config": "bsbm-alt",
                                  "traffic": "lookups", "chips": 1, "why": "x"})
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("bsbm-alt.lookups")
    manifest["per_layer"].append({"name": "rows_per_request.lookups", "unit": "rows",
                                  "better": "higher", "source": "program_counter",
                                  "layer": "batch operators", "moves": "qmph",
                                  "workloads": ["bsbm-alt.lookups"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == b for p, b in before.items())

    with off_chip(monkeypatch):
        plain = run_cell(root, "bsbm-alt.lookups", capsys, seed=SEED)
        traced = run_cell(root, "bsbm-alt.lookups", capsys, trace=1, seed=SEED)
    assert plain["correct"] and set(plain["metrics"]) == {"qmph", "setup_s"}
    assert traced["correct"] and traced["metrics"]["rows_per_request.lookups"]["value"] > 1


def test_a_configuration_whose_mix_binds_no_constants_is_added_by_files_alone(tmp_path):
    """The probe: its configuration, generator, queries, reference, mix and
    tiny cut as new files, its cell and metrics as new manifest entries; no
    existing file of the benchmark written."""
    source = copy_source(tmp_path / "source")
    before = {p: p.read_bytes() for p in (source / "bench").rglob("*") if p.is_file()}
    add_probe(source)
    assert all(p.read_bytes() == b for p, b in before.items())
    added = {p.relative_to(source / "bench").as_posix()
             for p in (source / "bench").rglob("*") if p.is_file() and p not in before}
    assert added == {"configs/probe-graph.json", "generators/probe_graph.py",
                     "queries/probe.json", "references/probe.py", "traffic/probe-counts.json",
                     "tests/tiny/probe-graph.json"}
    cell = Cell(make_root(tmp_path / "root", source), PROBE)
    assert cell.mix["bind"] == {} and cell.mix["control"]["kind"] == "approximate_numbers"
    assert tiny_cuts(source)["probe-graph"].items() <= cell.config["params"].items()


def test_a_configuration_without_a_tiny_cut_is_refused(tmp_path):
    source = add_probe(copy_source(tmp_path / "source"))
    (source / "bench" / "tests" / "tiny" / "probe-graph.json").unlink()
    with pytest.raises(ValueError, match="probe-graph"):
        make_root(tmp_path / "root", source)
    assert not (tmp_path / "root").exists()
