"""Kernel dispatch: per request, self time of its kernel dispatches after
their last device round trip: slicing and fixing up the outputs, from
the program's dispatch spans, nested dispatches charged to the innermost
(``bench/harness/phases.py``), mean over the traced window."""

from bench.harness import phases


def read(run):
    s = phases.mean_per_request(run, lambda tr: phases.phase_seconds(tr.dispatches)["finish"])
    return None if s is None else 1e3 * s
