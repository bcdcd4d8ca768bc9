"""gather_emit: gather the selected columns through (li, ri) and compare
each secondary-key pair once per emitted row."""

from bench.harness.roofline import io_bytes


def cost(result, lcols, rcols, li, ri, lsel=(), rsel=(), pairs=(), backend=None,
         out=None, out_offset=0):
    n = len(li)
    return n * (len(lsel) + len(rsel) + len(pairs)), io_bytes(result, lcols, rcols, li, ri)
