"""A copy of the benchmark at a tiny scale, and a run of one of its cells
on the CPU, for the harness's tests: the harness's look for a chip and
its compile cache are skipped, everything else runs as on the chip.

Each configuration's tiny cut is a file of its own,
``bench/tests/tiny/<config>.json``: the params it overrides, counts cut
and shapes kept (``bsbm-25m``: every count about 44x). The probe
(``bench/tests/probe/``, laid out as ``bench/``) is a configuration and
cell added by files and manifest entries alone, whose mix binds no
constants."""

from __future__ import annotations

import contextlib
import json
import pathlib
import shutil
from typing import Dict, Iterator

REPO = pathlib.Path(__file__).resolve().parents[2]
PROBE_FILES = REPO / "bench" / "tests" / "probe"

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = tuple(w["name"] for w in MANIFEST["workloads"])
PROBE = "probe-graph.counts"
PER_CELL = CELLS + (PROBE,)

SEED = 2**31 + 11  # larger than 32 signed bits hold


def tiny_cuts(source: pathlib.Path) -> Dict[str, dict]:
    """Each configuration's tiny cut under ``source``, by its name."""
    return {p.stem: json.loads(p.read_text())
            for p in sorted((source / "bench" / "tests" / "tiny").glob("*.json"))}


TINY = tiny_cuts(REPO)


def make_root(dest: pathlib.Path, source: pathlib.Path = REPO) -> pathlib.Path:
    """``dest`` holding ``source``'s ``BENCHMARK.json`` and ``bench/``
    with every configuration cut to its tiny cut. A configuration with no
    cut file is refused: it would run at full size."""
    manifest = json.loads((source / "BENCHMARK.json").read_text())
    cuts = tiny_cuts(source)
    missing = [c["name"] for c in manifest["configs"] if c["name"] not in cuts]
    if missing:
        raise ValueError(f"no tiny cut bench/tests/tiny/<config>.json for {missing}")
    shutil.copytree(source / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(source / "BENCHMARK.json", dest / "BENCHMARK.json")
    for c in manifest["configs"]:
        path = dest / c["file"]
        cfg = json.loads(path.read_text())
        cfg["params"].update(cuts[c["name"]])
        path.write_text(json.dumps(cfg))
    return dest


def copy_source(dest: pathlib.Path) -> pathlib.Path:
    """``dest`` holding the repository's ``BENCHMARK.json`` and all of
    ``bench/``, its tests too: a tree as a change to the benchmark sees it."""
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    return dest


def add_probe(source: pathlib.Path) -> pathlib.Path:
    """The probe added to ``source`` as a change to the benchmark would add
    a configuration: new files under ``bench/`` (its configuration,
    generator, queries, reference, mix and tiny cut), new ``configs``,
    ``workloads`` and ``per_layer`` entries, and the cell appended to each
    end-to-end metric's ``workloads``. No existing file is written."""
    for path in sorted(PROBE_FILES.rglob("*")):
        rel = path.relative_to(PROBE_FILES)
        if path.is_dir() or rel.name == "entries.json":
            continue
        dest = source / "bench" / rel
        dest.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, dest)
    entries = json.loads((PROBE_FILES / "entries.json").read_text())
    manifest = json.loads((source / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "per_layer"):
        manifest[key] += entries[key]
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += [w["name"] for w in entries["workloads"]]
    (source / "BENCHMARK.json").write_text(json.dumps(manifest, indent=2))
    return source


def probe_root(dest: pathlib.Path) -> pathlib.Path:
    """A tiny root that holds the probe beside every committed cell."""
    return make_root(dest / "root", add_probe(copy_source(dest / "source")))


def fake_tpu(chips: int) -> dict:
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": 1, "_device": None}


@contextlib.contextmanager
def off_chip(monkeypatch) -> Iterator[None]:
    from bench.harness import runner

    monkeypatch.setattr(runner, "find_devices", fake_tpu)
    monkeypatch.setattr(runner, "enable_compile_cache", lambda root: "off")
    yield


def run_cell(root: pathlib.Path, workload: str, capsys, seconds: float = 1.0, trace: int = 0,
             seed: int = SEED, plane: str = "numpy") -> dict:
    """One run of ``workload`` under ``root`` on the CPU; its last line."""
    from bench.harness import runner
    from repro.kernels import ops

    with ops.data_plane(plane):
        rc = runner.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                          "--trace", str(trace)], root=root)
    out = capsys.readouterr().out
    assert rc == 0, out[-2000:]
    return json.loads(out.strip().splitlines()[-1])
