"""Requests completed inside the window per second of the window: the
single-client throughput of a BSBM-style run."""


def read(run):
    return len(run.requests) / run.window_s
