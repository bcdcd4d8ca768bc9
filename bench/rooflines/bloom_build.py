"""bloom_build: one hash and one bit set per key."""

from bench.harness.roofline import io_bytes


def cost(result, keys, n_words=None, backend=None):
    return 2 * len(keys), io_bytes(result, keys)
