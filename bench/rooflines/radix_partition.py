"""radix_partition: one hash and one histogram increment per key."""

from bench.harness.roofline import io_bytes


def cost(result, keys, n_parts, backend=None):
    return 2 * len(keys), io_bytes(result, keys)
