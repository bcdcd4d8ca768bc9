"""Pallas TPU kernel: vectorized binary search over sorted keys.

The batch analogue of the storage seek behind skip() (paper §3.2 Skip
phase) and the probe-side lookup of the LookupJoin. position(q) = number of
keys < q (side='left') or <= q (side='right'), computed gather-free as a
comparison-matrix reduction, accumulated across key tiles through output
revisiting (TPU grids execute sequentially, so the (q_block, key_tile) grid
accumulates in-place in VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import tiling

Q_BLOCK = 1024
K_TILE = 2048
_PAD_KEY = np.iinfo(np.int32).max  # never counted


def _kernel(keys_ref, q_ref, out_ref, *, left: bool):
    k_idx = pl.program_id(1)
    keys = keys_ref[...]  # (K_TILE,)
    q = q_ref[...]  # (Q_BLOCK,)
    m = (keys[:, None] < q[None, :]) if left else (keys[:, None] <= q[None, :])
    counts = jnp.sum(m.astype(jnp.int32), axis=0)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = counts

    @pl.when(k_idx != 0)
    def _acc():
        out_ref[...] = out_ref[...] + counts


@functools.partial(jax.jit, static_argnames=("left", "interpret"))
def sorted_search_kernel(
    keys: jax.Array, queries: jax.Array, *, left: bool, interpret
) -> jax.Array:
    """Device entry: (M,) counts for tile-aligned padded keys and queries."""
    grid = (queries.shape[0] // Q_BLOCK, keys.shape[0] // K_TILE)
    return pl.pallas_call(
        functools.partial(_kernel, left=left),
        grid=grid,
        in_specs=[
            pl.BlockSpec((K_TILE,), lambda i, j: (j,)),
            pl.BlockSpec((Q_BLOCK,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((Q_BLOCK,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct(queries.shape, jnp.int32),
        interpret=interpret,
    )(keys, queries)


def sorted_search_pallas(
    keys, queries, side: str = "left", *, interpret
) -> np.ndarray:
    m = len(queries)
    out = tiling.round_trip(
        sorted_search_kernel,
        tiling.pad(keys, K_TILE, _PAD_KEY),
        tiling.pad(queries, Q_BLOCK, 0),
        left=(side == "left"),
        interpret=interpret,
    )
    return out[:m]
