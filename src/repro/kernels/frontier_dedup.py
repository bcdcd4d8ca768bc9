"""Pallas TPU kernel: frontier dedup for the property-path BFS engine.

One semi-naive BFS round produces a lexicographically sorted candidate
frontier of (source, node) int32 pairs; the delta frontier keeps a pair iff
it is (a) the first occurrence inside the batch and (b) not already in the
(sorted) visited set. (a) is a shifted-neighbor comparison; (b) is computed
gather-free as an equality-matrix reduction over visited tiles — the same
output-revisiting accumulation pattern as the sorted_search kernel (TPU
grids run sequentially, so the (cand_block, vis_tile) grid accumulates
match counts in-place in VMEM). Pairs stay as two int32 columns: no int64
composite key is ever formed, so the kernel runs with x64 disabled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import tiling

C_BLOCK = 1024
V_TILE = 2048
_PAD = np.iinfo(np.int32).min  # visited padding: matches no candidate


def _kernel(vh_ref, vl_ref, ch_ref, cl_ref, ph_ref, pl_ref, out_ref):
    v_idx = pl.program_id(1)
    vh, vl = vh_ref[...], vl_ref[...]  # (V_TILE,)
    ch, cl = ch_ref[...], cl_ref[...]  # (C_BLOCK,)
    hits = jnp.sum(
        ((vh[:, None] == ch[None, :]) & (vl[:, None] == cl[None, :])).astype(
            jnp.int32
        ),
        axis=0,
    )

    @pl.when(v_idx == 0)
    def _init():
        # fold the adjacent-unique test in on the first visited tile:
        # ph/pl carry each candidate's left neighbor (host-shifted, so the
        # test stays local to the block even at block boundaries)
        dup_prev = (ph_ref[...] == ch) & (pl_ref[...] == cl)
        out_ref[...] = hits + dup_prev.astype(jnp.int32)

    @pl.when(v_idx != 0)
    def _acc():
        out_ref[...] = out_ref[...] + hits


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_dedup_kernel(vh, vl, ch, cl, ph, pl_, *, interpret) -> jax.Array:
    """Device entry: (C,) hit counts over tile-aligned padded columns."""
    grid = (ch.shape[0] // C_BLOCK, vh.shape[0] // V_TILE)
    vis = pl.BlockSpec((V_TILE,), lambda i, j: (j,))
    cand = pl.BlockSpec((C_BLOCK,), lambda i, j: (i,))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[vis, vis, cand, cand, cand, cand],
        out_specs=cand,
        out_shape=jax.ShapeDtypeStruct(ch.shape, jnp.int32),
        interpret=interpret,
    )(vh, vl, ch, cl, ph, pl_)


def frontier_dedup_pallas(cand_hi, cand_lo, vis_hi, vis_lo, *, interpret
                          ) -> np.ndarray:
    """(C,) bool mask — see vecops.frontier_dedup for the contract."""
    c = len(cand_hi)
    cand_hi = np.asarray(cand_hi, np.int32)
    cand_lo = np.asarray(cand_lo, np.int32)
    # left-neighbor columns; the first candidate gets a sentinel neighbor
    ph = np.concatenate([[_PAD], cand_hi[:-1]]).astype(np.int32)
    pl_ = np.concatenate([[_PAD], cand_lo[:-1]]).astype(np.int32)
    counts = tiling.round_trip(
        frontier_dedup_kernel,
        tiling.pad(vis_hi, V_TILE, _PAD),
        tiling.pad(vis_lo, V_TILE, _PAD),
        tiling.pad(cand_hi, C_BLOCK, _PAD),
        tiling.pad(cand_lo, C_BLOCK, _PAD),
        tiling.pad(ph, C_BLOCK, _PAD),
        tiling.pad(pl_, C_BLOCK, _PAD),
        interpret=interpret,
    )
    return counts[:c] == 0
