"""Programs compiled or loaded from the persistent cache, counted from
JAX's monitoring events, with the name of each."""

from __future__ import annotations

from typing import List, Tuple


class CompileCounter:
    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.names: List[str] = []

    def on_duration(self, event: str, duration: float, fun_name: str = "?", **_kw) -> None:
        if event == self._COMPILE:
            self.programs += 1
            self.seconds += duration
            self.names.append(fun_name)

    def on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self.cache_hits += 1

    def snapshot(self) -> Tuple[int, int, float]:
        return self.programs, self.cache_hits, self.seconds
