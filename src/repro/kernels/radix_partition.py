"""Pallas TPU kernel: multiplicative-hash radix partitioning.

Assigns each key a partition id and builds the partition histogram — the
planning step of the distributed all_to_all exchange behind partitioned
joins and aggregations (DESIGN.md §2.1). The histogram accumulates across
the sequential TPU grid via output revisiting; counting is a gather-free
one-hot comparison-matrix reduction.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import tiling

BLOCK = 2048
_HASH_MULT = np.uint32(0x9E3779B1)
_PAD = np.iinfo(np.int32).min  # padding: pid -1, counted in no partition


def _kernel(keys_ref, pid_ref, hist_ref, *, n_parts: int):
    b = pl.program_id(0)
    keys = keys_ref[...]
    h = (keys.astype(jnp.uint32) * _HASH_MULT) >> np.uint32(16)
    pid = (h & np.uint32(n_parts - 1)).astype(jnp.int32)
    pid = jnp.where(keys == _PAD, -1, pid)
    pid_ref[...] = pid

    parts = jax.lax.iota(jnp.int32, n_parts)
    sel = parts[:, None] == pid[None, :]  # (P, BLOCK)
    counts = jnp.sum(sel.astype(jnp.int32), axis=1)

    @pl.when(b == 0)
    def _init():
        hist_ref[...] = counts

    @pl.when(b != 0)
    def _acc():
        hist_ref[...] = hist_ref[...] + counts


@functools.partial(jax.jit, static_argnames=("n_parts", "interpret"))
def radix_partition_kernel(
    keys: jax.Array, *, n_parts: int, interpret
) -> Tuple[jax.Array, jax.Array]:
    """Device entry over BLOCK-aligned padded keys: (pid, histogram)."""
    return pl.pallas_call(
        functools.partial(_kernel, n_parts=n_parts),
        grid=(keys.shape[0] // BLOCK,),
        in_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,))],
        out_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((n_parts,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(keys.shape, jnp.int32),
            jax.ShapeDtypeStruct((n_parts,), jnp.int32),
        ],
        interpret=interpret,
    )(keys)


def radix_partition_pallas(keys, n_parts: int, *, interpret
                           ) -> Tuple[np.ndarray, np.ndarray]:
    assert n_parts & (n_parts - 1) == 0, "n_parts must be a power of two"
    n = len(keys)
    pid, hist = tiling.round_trip(
        radix_partition_kernel, tiling.pad(keys, BLOCK, _PAD), n_parts=n_parts,
        interpret=interpret,
    )
    return pid[:n], hist
