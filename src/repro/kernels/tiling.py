"""Host-side padding of kernel inputs to power-of-two buckets.

Every Pallas entry point is jitted on its input shapes, so each new length
compiles a new program. The host wrappers pad every variable length up to
the next power of two, and never below the kernel's tile. That bounds the
compiled variants of a kernel to one per doubling of its inputs, and keeps
each length a multiple of the tile, as the TPU block specs require.
"""

from __future__ import annotations

import numpy as np

# smallest bucket of the build-side partition sort (``ops.hash_build``),
# an XLA sort on the device rather than a Pallas kernel
SORT_TILE = 1024


def bucket(n: int, tile: int) -> int:
    """Smallest power of two >= max(n, tile); ``tile`` is a power of two."""
    return max(tile, 1 << max(int(n) - 1, 0).bit_length())


def pad(a, tile: int, fill, dtype=np.int32) -> np.ndarray:
    """Copy of ``a`` as ``dtype`` with its last axis padded with ``fill``
    to ``bucket(len, tile)``."""
    a = np.asarray(a, dtype=dtype)
    n = a.shape[-1]
    out = np.full(a.shape[:-1] + (bucket(n, tile),), fill, dtype=dtype)
    out[..., :n] = a
    return out
