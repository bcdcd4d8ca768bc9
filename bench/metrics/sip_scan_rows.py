"""Batch operators: per request, the rows read from the store by index
scans that consume a SIP filter (``QueryTrace.sip_scan_rows``), mean over
the traced window. A program without the counter reads as None."""

from bench.harness import phases


def read(run):
    try:
        return phases.mean_per_request(run, lambda tr: tr.sip_scan_rows)
    except AttributeError:
        return None
