"""Kernel dispatches per request, from each request's kernel ledger
(nested dispatches count each), mean over the window."""


def read(run):
    if not run.requests:
        return None
    return sum(r.dispatches for r in run.requests) / len(run.requests)
