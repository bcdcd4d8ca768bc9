"""Sideways information passing: the runtime SipFilter handle (DESIGN.md §12).

A SipFilter carries a summary of a join's build side — its sorted unique
keys, their min/max code range, and a blocked bloom filter over them built
on first use — from the join that produces it *sideways* into the
probe-side Scan/PathExpand leaves, which consume it before the join ever
sees their rows:

  * sorted leaves seek straight to the runs of the build keys when the
    keys are sparse in the range they cover; otherwise they narrow to the
    code range through the skip() machinery (seek to lo, stop past hi)
    and bloom-mask inside it;
  * unsorted leaves apply the range + bloom membership test as a batch
    mask (no false negatives, so this is a pure prefilter: both engines
    return exactly the same multiset with SIP on or off).

The filter is lazy: the translator binds a provider closure onto the
exporting join, and the first consuming leaf forces it. For a HashJoin the
provider runs the build phase (already materialized before any probe batch
is pulled); for a MergeJoin whose build side is a Sort pipeline breaker it
forces the sort's materialization; a merely-sorted build side yields a
range-only filter (its min/max keys are O(1) reads off the index).

Providers return ("keys", np.ndarray) for a full keys+bloom+range summary,
("range", lo, hi) for range-only, or None when nothing can be derived —
the filter then stays a pass-through forever.
"""

from __future__ import annotations

from time import perf_counter
from typing import Callable, Optional, Tuple

import numpy as np

from repro.core import vecops
from repro.kernels import ops as KOPS


class SipFilter:
    def __init__(self, var: int, sid: int = 0, backend: Optional[str] = None):
        self.var = var
        self.sid = sid
        self.backend = backend
        self._provider: Optional[Callable] = None
        self._ready = False
        self._available = False
        # sorted unique build keys; None for a range-only payload
        self.keys: Optional[np.ndarray] = None
        # bloom words over ``keys``, built by the first mask() that needs
        # them, sized by the build side's rows (``_n_build``) and not its
        # distinct keys: the word count is a compiled shape, and a
        # template's build sides keep their row count from one request to
        # the next where their distinct keys do not
        self.words: Optional[np.ndarray] = None
        self._n_build = 0
        self.lo = 0
        self.hi = -1  # (0, -1) == provably empty build side
        # counters surfaced through OpStats.extra by the consuming leaves
        self.rows_tested = 0
        self.rows_pruned = 0
        self.probe_dispatches = 0
        self.build_ms = 0.0

    # -- producer side -----------------------------------------------------

    def bind(self, provider: Callable) -> None:
        """Attach the build-side summary provider (translator wiring)."""
        self._provider = provider

    def reset(self) -> None:
        """Invalidate the summary (the exporting join was reset)."""
        self._ready = False
        self._available = False
        self.keys = self.words = None
        self.lo, self.hi = 0, -1

    def ensure(self) -> None:
        if self._ready:
            return
        self._ready = True
        payload = self._provider() if self._provider is not None else None
        if payload is None:
            return  # pass-through: nothing derivable from the build side
        t0 = perf_counter()
        if payload[0] == "keys":
            self.keys = np.unique(np.asarray(payload[1], dtype=np.int32))
            self._n_build = len(payload[1])
            if len(self.keys):
                self.lo, self.hi = int(self.keys[0]), int(self.keys[-1])
        else:  # ("range", lo, hi)
            _, self.lo, self.hi = payload
        self.build_ms += (perf_counter() - t0) * 1e3
        self._available = True

    # -- consumer side -----------------------------------------------------

    def code_range(self) -> Optional[Tuple[int, int]]:
        """(lo, hi) inclusive build-key range, or None for pass-through.
        hi < lo means the build side is empty: nothing can match."""
        self.ensure()
        return (self.lo, self.hi) if self._available else None

    def mask(self, codes: np.ndarray) -> Optional[np.ndarray]:
        """Bool keep-mask over ``codes`` (range + bloom membership), or
        None for pass-through. Conservative: may keep non-members (bloom
        false positives), never drops a member."""
        self.ensure()
        if not self._available:
            return None
        m = (codes >= self.lo) & (codes <= self.hi)
        if self.keys is not None and m.any():
            if self.words is None:
                t0 = perf_counter()
                self.words = KOPS.bloom_build(
                    self.keys, vecops.bloom_n_words(self._n_build), backend=self.backend
                )[0]
                self.build_ms += (perf_counter() - t0) * 1e3
            self.probe_dispatches += 1
            m &= KOPS.bloom_probe(self.words, codes, backend=self.backend)
        self.rows_tested += len(codes)
        self.rows_pruned += int(len(codes) - m.sum())
        return m
