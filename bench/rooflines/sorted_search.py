"""sorted_search: a binary search of each query over the sorted keys."""

from bench.harness.roofline import io_bytes, log2_ceil


def cost(result, keys, queries, side="left", backend=None):
    return len(queries) * log2_ceil(len(keys)), io_bytes(result, keys, queries)
