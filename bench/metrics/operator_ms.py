"""Batch operators: per request, the engine's translate and execute spans
less the kernel calls inside them, mean over the traced window."""


def read(run):
    ms = run.mean_layer("operator")
    return None if ms is None else 1e3 * ms
