"""Median client-side latency over all requests completed in the window."""

from bench.harness import stats


def read(run):
    return 1e3 * stats.percentile([r.latency_s for r in run.requests], 50)
