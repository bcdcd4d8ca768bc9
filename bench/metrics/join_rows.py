"""Batch operators: per request, the rows join operators emitted
(``QueryTrace.join_rows``), mean over the traced window. A program
without the counter reads as None."""

from bench.harness import phases


def read(run):
    try:
        return phases.mean_per_request(run, lambda tr: tr.join_rows)
    except AttributeError:
        return None
