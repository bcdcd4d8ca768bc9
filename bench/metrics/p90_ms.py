"""90th-percentile client-side latency over all requests completed in the
window (nearest rank)."""

from bench.harness import stats


def read(run):
    return 1e3 * stats.percentile([r.latency_s for r in run.requests], 90)
