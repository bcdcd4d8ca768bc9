"""Batch operators: per request, the index scans that sought their SIP
build keys' runs instead of reading the keys' whole code range
(``QueryTrace.sip_seeks``), mean over the traced window. A program
without the counter reads as None."""

from bench.harness import phases


def read(run):
    try:
        return phases.mean_per_request(run, lambda tr: tr.sip_seeks)
    except AttributeError:
        return None
