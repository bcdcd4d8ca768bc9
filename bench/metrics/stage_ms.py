"""Kernel dispatch: per request, self time of its kernel dispatches outside
their device round trips, up to the end of each one's last round trip:
padding and the other host work before a launch, from the program's
dispatch spans, nested dispatches charged to the innermost
(``bench/harness/phases.py``), mean over the traced window."""

from bench.harness import phases


def read(run):
    s = phases.mean_per_request(run, lambda tr: phases.phase_seconds(tr.dispatches)["stage"])
    return None if s is None else 1e3 * s
