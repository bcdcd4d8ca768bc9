"""Run one benchmark cell on the chip and print its result as the last
line of standard output.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. ``bench/harness/runner.py`` says what a
run does; ``BENCHMARK.json`` lists the cells.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime logs under /tmp/tpu_logs unless told otherwise: a run
# writes nothing outside its checkout and its own temporary directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench.harness import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.main(root=ROOT, t_start=T_START))
