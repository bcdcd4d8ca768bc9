"""frontier_dedup: one adjacent compare per candidate and a binary search
of the visited set."""

from bench.harness.roofline import io_bytes, log2_ceil


def cost(result, cand_hi, cand_lo, vis_hi, vis_lo, backend=None):
    n = len(cand_lo)
    return n * (1 + log2_ceil(len(vis_lo))), io_bytes(result, cand_hi, cand_lo, vis_hi, vis_lo)
