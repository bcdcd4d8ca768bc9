"""A run never measures off the chip: it exits non-zero and prints no
result where JAX finds no TPU, where the cell asks for more chips than
JAX finds, where the chip's kind has no peaks, and in a checkout that
holds only the benchmark's own files."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench.harness import runner
from bench.tests.tiny_bench import REPO


def run_cli(cwd: pathlib.Path, program: bool = True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    if program:
        env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bsbm-25m.explore", "--seed", "7",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(out: str) -> bool:
    for line in out.strip().splitlines()[-1:]:
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_refuses_without_a_tpu():
    proc = run_cli(REPO)
    assert proc.returncode != 0
    assert no_result(proc.stdout)
    assert "TPU" in proc.stderr


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    proc = run_cli(tmp_path, program=False)
    assert proc.returncode != 0
    assert no_result(proc.stdout)


def test_refuses_fewer_chips_than_the_cell_asks_for(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    assert runner.find_devices(1)["kind"] == "TPU v5 lite"
    with pytest.raises(SystemExit):
        runner.find_devices(4)


def test_refuses_a_device_kind_without_peaks():
    cell = runner.Cell(REPO, "bsbm-25m.explore")
    assert cell.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        cell.peaks("TPU v9 imaginary")
