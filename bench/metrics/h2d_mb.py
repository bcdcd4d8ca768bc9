"""Kernel dispatch: per request, the bytes of host numpy arrays handed to
device programs (``h2d_bytes`` of each dispatch), in MB (1e6 bytes),
mean over the traced window."""

from bench.harness import phases


def read(run):
    b = phases.mean_per_request(run, lambda tr: sum(d.h2d_bytes for d in tr.dispatches))
    return None if b is None else b / 1e6
