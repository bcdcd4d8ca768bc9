"""Batch operators: the solutions a row emitted by a join operator stands
for, over the traced window: Σ of those rows' weights
(``QueryTrace.join_weight``) over their number (``join_rows``). 1 where
joins emit every solution; more where weighted counting merged equal
rows. A program without the counters, or whose joins emitted nothing,
reads as None."""

from bench.harness import phases


def read(run):
    pairs = phases.window_traces(run)
    if pairs is None:
        return None
    try:
        rows = sum(tr.join_rows for _, tr in pairs)
        weight = sum(tr.join_weight for _, tr in pairs)
    except AttributeError:
        return None
    return weight / rows if rows else None
