"""Pallas TPU kernel: fused join emission (gather_emit, DESIGN.md §2.3).

One kernel dispatch materializes an output block of a join: gather the
emitted left/right source rows through the (li, ri) index vectors, NULL-
extend virtual right rows (ri == -1, the left_outer padding), and evaluate
the secondary join-key equality pairs into the combined validity mask —
the work MergeJoin/LookupJoin emission previously did column-by-column in
Python with intermediate whole-window materializations.

TPU adaptation: random-access gathers are HBM-latency-bound, so — like
join_expand.py — the gather is computed **gather-free**: the source is
streamed chunk-by-chunk through VMEM and each chunk contributes a one-hot
comparison-matrix select-accumulate into the resident output tile. Every
index hits exactly one chunk, so summing partials over the chunk axis of
the grid reconstructs the gather exactly. The secondary-key mask and the
virtual-row NULL fill run in the same kernel on the final chunk, while the
gathered tile is still in VMEM — that is the fusion.

Grid: (n_output_blocks, n_source_chunks). The chunk axis is the inner
one, so each output tile stays resident in VMEM while every chunk streams
past it: the TPU writes an output block back when its index changes and
never reads it in again.

Layout contract (enforced by the kernels.ops wrapper): the *emitted* rows
of each source come first and the rows referenced by the k-th equality
pair sit at tail position K - n_pairs + k of their source.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import tiling

N_TILE = 1024  # source rows streamed per chunk
BLOCK = 1024  # output slots per grid step

_NULL = -1


def _kernel(lsrc_ref, rsrc_ref, li_ref, ri_ref, lout_ref, rout_ref, mask_ref,
            *, n_pairs: int, n_chunks: int):
    nc = pl.program_id(1)
    n0 = nc * N_TILE
    li = li_ref[...]  # (BLOCK,)
    ri = ri_ref[...]
    offs = jax.lax.iota(jnp.int32, N_TILE)

    # one-hot chunk-local selects; indices outside [n0, n0+N_TILE) (and the
    # virtual ri == -1 rows) match nothing and contribute zero
    sel_l = (li[None, :] - n0) == offs[:, None]  # (N_TILE, BLOCK)
    sel_r = (ri[None, :] - n0) == offs[:, None]

    kl = lsrc_ref.shape[0]
    kr = rsrc_ref.shape[0]
    partial_l = jnp.stack(
        [jnp.sum(jnp.where(sel_l, lsrc_ref[k][:, None], 0), axis=0) for k in range(kl)]
    )
    partial_r = jnp.stack(
        [jnp.sum(jnp.where(sel_r, rsrc_ref[k][:, None], 0), axis=0) for k in range(kr)]
    )

    @pl.when(nc == 0)
    def _init():
        lout_ref[...] = partial_l
        rout_ref[...] = partial_r

    @pl.when(nc != 0)
    def _accumulate():
        lout_ref[...] += partial_l
        rout_ref[...] += partial_r

    @pl.when(nc == n_chunks - 1)
    def _finalize():  # mask + NULL-extension while the tile is in VMEM
        lg = lout_ref[...]
        rg = rout_ref[...]
        virtual = ri < 0
        m = jnp.ones_like(ri)
        for p in range(n_pairs):
            eq = lg[kl - n_pairs + p] == rg[kr - n_pairs + p]
            m = m * jnp.where(virtual | eq, 1, 0)
        rout_ref[...] = jnp.where(virtual[None, :], _NULL, rg)
        mask_ref[...] = m


@functools.partial(jax.jit, static_argnames=("n_pairs", "interpret"))
def gather_emit_kernel(
    lsrc: jax.Array,  # (KL, N) int32: emit rows first, pair-left rows at tail
    rsrc: jax.Array,  # (KR, N) int32: emit rows first, pair-right rows at tail
    li: jax.Array,  # (C,) int32
    ri: jax.Array,  # (C,) int32; -1 = virtual NULL right row
    *,
    n_pairs: int,
    interpret,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Device entry over tile-aligned padded inputs (N a multiple of
    N_TILE, C of BLOCK): (lout (KL, C), rout (KR, C), mask (C,) int32)."""
    kl, n = lsrc.shape
    kr = rsrc.shape[0]
    c = li.shape[0]
    n_chunks = n // N_TILE
    grid = (c // BLOCK, n_chunks)
    src_l = pl.BlockSpec((kl, N_TILE), lambda cb, nc: (0, nc))
    src_r = pl.BlockSpec((kr, N_TILE), lambda cb, nc: (0, nc))
    idx = pl.BlockSpec((BLOCK,), lambda cb, nc: (cb,))
    out_l = pl.BlockSpec((kl, BLOCK), lambda cb, nc: (0, cb))
    out_r = pl.BlockSpec((kr, BLOCK), lambda cb, nc: (0, cb))
    return pl.pallas_call(
        functools.partial(_kernel, n_pairs=n_pairs, n_chunks=n_chunks),
        grid=grid,
        in_specs=[src_l, src_r, idx, idx],
        out_specs=[out_l, out_r, idx],
        out_shape=[
            jax.ShapeDtypeStruct((kl, c), jnp.int32),
            jax.ShapeDtypeStruct((kr, c), jnp.int32),
            jax.ShapeDtypeStruct((c,), jnp.int32),
        ],
        interpret=interpret,
    )(lsrc, rsrc, li, ri)


def gather_emit_pallas(lsrc, rsrc, li, ri, n_pairs: int, *, interpret
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Returns (lout (KL, C), rout (KR, C), mask (C,) int32)."""
    c = len(li)
    n = max(lsrc.shape[1], rsrc.shape[1], 1)
    lsrc = tiling.pad(lsrc, tiling.bucket(n, N_TILE), 0)
    rsrc = tiling.pad(rsrc, tiling.bucket(n, N_TILE), 0)
    # pad li with 0 (a real row; the padded output slots are sliced off) and
    # ri with -1 (virtual, selects nothing)
    lout, rout, mask = tiling.round_trip(
        gather_emit_kernel, lsrc, rsrc, tiling.pad(li, BLOCK, 0),
        tiling.pad(ri, BLOCK, _NULL), n_pairs=n_pairs, interpret=interpret,
    )
    return lout[:, :c], rout[:, :c], mask[:c]
