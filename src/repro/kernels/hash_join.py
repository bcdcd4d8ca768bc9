"""Pallas TPU kernel: hash-join probe over a radix-partitioned build side.

The build side is laid out by ``hash_build`` (kernels.ops): rows grouped by
multiplicative-hash partition id — the radix_partition kernel supplies the
ids and the histogram — and key-sorted within each partition, so a probe
key's matches occupy one contiguous run. This kernel locates that run.

TPU adaptation: a per-probe binary search is a chain of data-dependent
HBM gathers — the exact access pattern the hardware punishes. Instead the
run boundaries are computed **gather-free** by *counting*: in the
(partition, key) lexicographic order, a probe's run starts at the number
of build rows that order strictly below it and ends at the number that
order at-or-below it. Build rows stream tile-by-tile through VMEM and each
tile contributes a comparison-matrix count to the resident (lo, hi)
output block — the same tiled select-accumulate idiom as gather_emit and
frontier_dedup. Keys are int32 (hi, lo) pairs compared lexicographically
(hi >= 0, see vecops §11 header); no int64 anywhere, x64 stays off.

Grid: (n_probe_blocks, n_build_tiles). The build-tile axis is the inner
one, so each output block stays resident in VMEM while every build tile
streams past it: the TPU writes an output block back when its index
changes and never reads it in again. Build padding rows carry
pid = INT32_MAX, which orders above every real (pid < n_parts) probe and
therefore contributes zero to both counts.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import tiling

N_TILE = 2048  # build rows streamed per chunk
BLOCK = 1024  # probe keys per grid step

_PAD_PID = np.int32(np.iinfo(np.int32).max)


def _kernel(bpid_ref, bhi_ref, blo_ref, qpid_ref, qhi_ref, qlo_ref,
            lo_ref, hi_ref):
    nc = pl.program_id(1)
    bp, bh, bl = bpid_ref[...], bhi_ref[...], blo_ref[...]  # (N_TILE,)
    qp, qh, ql = qpid_ref[...], qhi_ref[...], qlo_ref[...]  # (BLOCK,)

    # (N_TILE, BLOCK) triple-lexicographic comparison matrices
    bp2, qp2 = bp[:, None], qp[None, :]
    bh2, qh2 = bh[:, None], qh[None, :]
    bl2, ql2 = bl[:, None], ql[None, :]
    lt = (bp2 < qp2) | (
        (bp2 == qp2) & ((bh2 < qh2) | ((bh2 == qh2) & (bl2 < ql2)))
    )
    eq = (bp2 == qp2) & (bh2 == qh2) & (bl2 == ql2)
    n_lt = jnp.sum(lt.astype(jnp.int32), axis=0)
    n_le = n_lt + jnp.sum(eq.astype(jnp.int32), axis=0)

    @pl.when(nc == 0)
    def _init():
        lo_ref[...] = n_lt
        hi_ref[...] = n_le

    @pl.when(nc != 0)
    def _acc():
        lo_ref[...] += n_lt
        hi_ref[...] += n_le


@functools.partial(jax.jit, static_argnames=("interpret",))
def hash_probe_kernel(
    bpid: jax.Array,  # (N,) int32 build partition ids, partition-grouped
    bhi: jax.Array,  # (N,) int32 build key hi (>= 0), sorted within pid
    blo: jax.Array,  # (N,) int32 build key lo
    qpid: jax.Array,  # (C,) int32 probe partition ids
    qhi: jax.Array,  # (C,) int32 probe key hi
    qlo: jax.Array,  # (C,) int32 probe key lo
    *,
    interpret,
) -> Tuple[jax.Array, jax.Array]:
    """Device entry: (lo, hi) run boundaries over tile-aligned padded
    inputs (N a multiple of N_TILE, C of BLOCK)."""
    c = qpid.shape[0]
    grid = (c // BLOCK, bpid.shape[0] // N_TILE)
    src = pl.BlockSpec((N_TILE,), lambda cb, nc: (nc,))
    qry = pl.BlockSpec((BLOCK,), lambda cb, nc: (cb,))
    out = pl.BlockSpec((BLOCK,), lambda cb, nc: (cb,))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[src, src, src, qry, qry, qry],
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((c,), jnp.int32),
            jax.ShapeDtypeStruct((c,), jnp.int32),
        ],
        interpret=interpret,
    )(bpid, bhi, blo, qpid, qhi, qlo)


def hash_probe_pallas(bpid, bhi, blo, qpid, qhi, qlo, *, interpret
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (lo, hi) int32 run boundaries per probe key. Build padding
    rows carry pid = INT32_MAX and count for no probe."""
    c = len(qpid)
    lo, hi = tiling.round_trip(
        hash_probe_kernel,
        tiling.pad(bpid, N_TILE, _PAD_PID),
        tiling.pad(bhi, N_TILE, 0),
        tiling.pad(blo, N_TILE, 0),
        tiling.pad(qpid, BLOCK, 0),
        tiling.pad(qhi, BLOCK, 0),
        tiling.pad(qlo, BLOCK, 0),
        interpret=interpret,
    )
    return lo[:c], hi[:c]
