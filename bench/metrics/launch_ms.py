"""Kernel dispatch: per request, the jitted calls of its kernel dispatches'
device round trips: argument transfer, dispatch and any compile, from
the program's dispatch spans, nested dispatches charged to the innermost
(``bench/harness/phases.py``), mean over the traced window."""

from bench.harness import phases


def read(run):
    s = phases.mean_per_request(run, lambda tr: phases.phase_seconds(tr.dispatches)["launch"])
    return None if s is None else 1e3 * s
