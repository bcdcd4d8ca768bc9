"""Kernel dispatch: share of the bytes that the kernels' host wrappers
pad their inputs to (``kernels/tiling.py`` ``pad``) that is padding,
100 × Σ(padded − logical) ÷ Σ padded over the traced window's requests."""

from bench.harness import phases


def read(run):
    pairs = phases.window_traces(run)
    if pairs is None:
        return None
    padded = sum(d.pad_bytes for _, tr in pairs for d in tr.dispatches)
    logical = sum(d.pad_logical_bytes for _, tr in pairs for d in tr.dispatches)
    return None if padded == 0 else 100.0 * (padded - logical) / padded
