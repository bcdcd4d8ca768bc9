"""hash_probe: a search of each probe key among the build keys."""

from bench.harness.roofline import io_bytes, log2_ceil


def cost(result, spid, skey_hi, skey_lo, qkey_hi, qkey_lo, part_starts, n_parts,
         backend=None, cache=None):
    return (2 * len(qkey_lo) * log2_ceil(len(skey_lo)),
            io_bytes(result, spid, skey_hi, skey_lo, qkey_hi, qkey_lo, part_starts))
