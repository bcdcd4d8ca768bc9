"""bloom_probe: one hash and one bit test per query."""

from bench.harness.roofline import io_bytes


def cost(result, words, queries, backend=None):
    return 2 * len(queries), io_bytes(result, words, queries)
