"""Query mixes per hour, BSBM's single-client throughput: an hour over
the time one query mix takes, that is each query's mean client-side
latency over all its requests completed in the window, weighted by how
often the mix sends it. Unlike requests per second of the window, it does
not hang on where the window cuts the mix. None if a query of the mix
completed no request."""

import collections


def read(run):
    lat = collections.defaultdict(list)
    for r in run.requests:
        lat[r.query].append(r.latency_s)
    weights = collections.Counter(run.mix)
    if not weights or any(q not in lat for q in weights):
        return None
    mix_s = sum(k * sum(lat[q]) / len(lat[q]) for q, k in weights.items())
    return 3600.0 / mix_s
