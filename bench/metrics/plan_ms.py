"""Server and planner: per request, the program's ``plan`` span (planning
and the template fingerprint; none on a plan-cache hit), mean over the
traced window."""

from bench.harness import phases


def read(run):
    s = phases.mean_per_request(run, lambda tr: phases.span_seconds(tr, "plan"))
    return None if s is None else 1e3 * s
