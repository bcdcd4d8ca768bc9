"""Logical bytes of a kernel call, shared by ``bench/rooflines/*.py``:
every array the operator hands the call, unpadded and read once, plus
every array the call returns, written once. Elements count at the
device's 4 bytes (int32 and float32) and masks at one byte."""

from __future__ import annotations

import numpy as np


def elements(x) -> int:
    if isinstance(x, np.ndarray):
        return int(x.size)
    if isinstance(x, (tuple, list)):
        return sum(elements(v) for v in x)
    return 0


def out_bytes(result) -> int:
    if isinstance(result, np.ndarray):
        return int(result.size) * (1 if result.dtype == np.bool_ else 4)
    if isinstance(result, (tuple, list)):
        return sum(out_bytes(v) for v in result)
    return 0


def io_bytes(result, *inputs) -> int:
    return 4 * sum(elements(x) for x in inputs) + out_bytes(result)


def log2_ceil(n: int) -> int:
    return max(1, int(np.ceil(np.log2(max(int(n), 1) + 1))))
