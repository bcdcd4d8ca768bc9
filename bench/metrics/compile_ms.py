"""Kernel dispatch: per request, the compile seconds the program charged
inside it (to the dispatch that caused each compile, or to the request
outside any), in ms, mean over the traced window."""

from bench.harness import phases


def read(run):
    s = phases.mean_per_request(run, phases.compile_seconds)
    return None if s is None else 1e3 * s
