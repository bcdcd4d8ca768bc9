"""hash_build's own work, the sort of the build rows by (partition, key):
n log2 n compares. Its nested radix_partition call is counted there."""

from bench.harness.roofline import io_bytes, log2_ceil


def cost(result, key_hi, key_lo, n_parts, backend=None):
    n = len(key_lo)
    return n * log2_ceil(n), io_bytes(result, key_hi, key_lo)
