"""A copy of the benchmark at a tiny scale, and a run of one of its cells
on the CPU, for the harness's tests: the harness's look for a chip and
its compile cache are skipped, everything else runs as on the chip."""

from __future__ import annotations

import contextlib
import json
import pathlib
import shutil
from typing import Iterator

REPO = pathlib.Path(__file__).resolve().parents[2]

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = tuple(w["name"] for w in MANIFEST["workloads"])

# every count cut about 44x from the configuration's; the shapes stay
TINY = {
    "bsbm-25m": dict(products=400, product_types=12, product_features=135, producers=8,
                     vendors=5, reviewers=205, rating_sites=3),
}

SEED = 2**31 + 11  # larger than 32 signed bits hold


def make_root(dest: pathlib.Path) -> pathlib.Path:
    """``dest`` holding ``BENCHMARK.json`` and ``bench/`` with every
    configuration cut to its ``TINY`` counts."""
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    for name, params in TINY.items():
        path = dest / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["params"].update(params)
        path.write_text(json.dumps(cfg))
    return dest


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
