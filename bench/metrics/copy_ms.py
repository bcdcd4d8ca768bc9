"""Kernel dispatch: per request, its kernel dispatches' copies of their
outputs to host numpy, from the program's dispatch spans, nested
dispatches charged to the innermost (``bench/harness/phases.py``), mean
over the traced window."""

from bench.harness import phases


def read(run):
    s = phases.mean_per_request(run, lambda tr: phases.phase_seconds(tr.dispatches)["copy"])
    return None if s is None else 1e3 * s
