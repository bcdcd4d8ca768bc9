"""Server and planner: per request, the client-side time outside the
engine's translate and execute spans (parse, plan, plan-cache lookup,
the server's bookkeeping), mean over the traced window."""


def read(run):
    ms = run.mean_layer("host")
    return None if ms is None else 1e3 * ms
