"""Cartesian product for disconnected plan fragments (rare; the planner
only emits it when no join variable exists). Reuses the Build-phase
expansion machinery with a single group spanning both sides."""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from repro.core import vecops
from repro.core.batch import BatchPool, ColumnBatch, bucket_for
from repro.core.operators.base import BatchOperator
from repro.core.operators.sort import materialize


class CrossJoin(BatchOperator):
    emits_join_rows = True

    def __init__(
        self,
        left: BatchOperator,
        right: BatchOperator,
        pool: Optional[BatchPool] = None,
    ):
        self.left = left
        self.right = right
        self.pool = pool
        lv = tuple(left.var_ids())
        self._right_out = tuple(v for v in right.var_ids() if v not in lv)
        self._vars = lv + self._right_out
        self._lcols: Optional[np.ndarray] = None
        self._rcols: Optional[np.ndarray] = None
        self._emitted = 0
        super().__init__("Cross", "")

    def var_ids(self) -> Tuple[int, ...]:
        return self._vars

    def children(self) -> List[BatchOperator]:
        return [self.left, self.right]

    def _ensure(self) -> None:
        if self._lcols is None:
            self._lvars, self._lcols = materialize(self.left)
            self._rvars, self._rcols = materialize(self.right)

    def _next(self) -> Optional[ColumnBatch]:
        self._ensure()
        nl, nr = self._lcols.shape[1], self._rcols.shape[1]
        total = nl * nr
        if self._emitted >= total:
            return None
        cap = bucket_for(4096)
        count = min(cap, total - self._emitted)
        cum = np.asarray([0, total], dtype=np.int64)
        li, ri = vecops.expand_cross(
            np.zeros(1, dtype=np.int32),
            np.asarray([nl], dtype=np.int32),
            np.zeros(1, dtype=np.int32),
            np.asarray([nr], dtype=np.int32),
            cum,
            self._emitted,
            count,
        )
        self._emitted += count
        cols = [self._lcols[self._lvars.index(v), li] for v in self._lvars]
        for v in self._right_out:
            cols.append(self._rcols[self._rvars.index(v), ri])
        return ColumnBatch.from_columns(self._vars, cols, None, pool=self.pool)

    def _reset(self) -> None:
        self.left.reset()
        self.right.reset()
        self._lcols = None
        self._emitted = 0
