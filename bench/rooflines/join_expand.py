"""join_expand: one (left, right) index pair written per emitted row."""

from bench.harness.roofline import io_bytes


def cost(result, lstarts, llens, rstarts, rlens, cum, base, count, backend=None):
    return 2 * count, io_bytes(result, lstarts, llens, rstarts, rlens, cum)
