"""segment_reduce: one key compare and one accumulate per input row."""

from bench.harness.roofline import io_bytes


def cost(result, keys, values, func, backend=None, seg=None):
    return 2 * len(keys), io_bytes(result, keys, values)
