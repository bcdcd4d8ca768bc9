"""Readings of a cell's control: the plain reference put in the program's
place with one guarantee broken (``check.control_answers``), judged
against the reference as a run judges the program. It sets the upper
reading of each limit; the benchmark's own runs never run it.

    python bench/control.py --workload <name> --seed <n> [--seed <n> ...]

Each seed builds the cell's store at its own size and answers the first
``control_requests`` requests of the window's traffic (about as many as
a run completes). One JSON line per seed, with the numbers compared.
"""

from __future__ import annotations

import argparse
import itertools
import json
import pathlib
import sys
import time
from typing import Optional, Sequence

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(root: pathlib.Path, workload: str, seed: int, n_requests: Optional[int] = None) -> dict:
    from bench.harness import check
    from bench.harness.runner import Cell, load_module
    from bench.harness.traffic import Traffic

    cell = Cell(root, workload)
    ds = cell.generator.generate(cell.config["params"], seed)
    n = n_requests or cell.mix["control_requests"]
    requests = list(itertools.islice(Traffic(cell.mix, cell.queries, ds, seed).window(), n))
    reference = load_module(cell.reference_path).Reference(ds)
    t = time.perf_counter()
    answers = check.control_answers(cell.mix["control"], reference, cell.queries, requests)
    correct, numbers = check.judge(answers, reference, cell.queries,
                                   [r.bind for r in requests], 0, cell.mix["limits"])
    return {"workload": workload, "seed": seed, "requests": len(requests),
            "control": cell.mix["control"], "correct": correct, "check": numbers,
            "seconds": time.perf_counter() - t}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description="Readings of a cell's control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    for seed in args.seed:
        print(json.dumps(readings(ROOT, args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
