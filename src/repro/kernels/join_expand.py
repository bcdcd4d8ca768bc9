"""Pallas TPU kernel: merge-join Build-phase expansion (paper §3.2).

Materializes output slots [base, base+count) of a grouped cross product as
(left_idx, right_idx) gather indices. This is the hot loop of the paper —
the top merge join of LSQB Q6 emits 288M rows through it (Listing 5).

TPU adaptation: the per-slot binary search over cumulative group offsets and
the per-group parameter gathers are computed **gather-free** as comparison
matrices + select-accumulate over the group axis — pure VPU int32 ops on
(G_TILE, BLOCK) tiles held in VMEM, no dynamic indexing. One-hot selects
replace random-access loads, which is the idiomatic TPU trade (HBM gathers
are latency-bound; VMEM-resident broadcast-compare-reduce is throughput-
bound). See DESIGN.md §2.

Grid: (num_output_blocks,). Per call, G <= G_MAX groups (the ops.py wrapper
splits larger probes into group chunks).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import tiling

BLOCK = 1024  # output slots per grid step
G_MAX = 2048  # max groups per kernel invocation (VMEM: G_MAX*BLOCK*4B tiles)


def _kernel(cum_hi_ref, cum_lo_ref, lstarts_ref, rstarts_ref, rlens_ref,
            base_ref, total_ref, li_ref, ri_ref):
    b = pl.program_id(0)
    g_tile = cum_hi_ref.shape[0]
    t = base_ref[0] + b * BLOCK + jax.lax.iota(jnp.int32, BLOCK)  # (BLOCK,)

    # group id = #groups whose output range ends at/before t
    cum_hi = cum_hi_ref[...]  # (G,) end offset of each group's output
    m = cum_hi[:, None] <= t[None, :]  # (G, BLOCK) comparison matrix
    gid = jnp.sum(m.astype(jnp.int32), axis=0)  # (BLOCK,)

    # one-hot select of per-group parameters (gather-free)
    gids = jax.lax.iota(jnp.int32, g_tile)
    sel = gids[:, None] == gid[None, :]  # (G, BLOCK)

    def pick(ref):
        return jnp.sum(jnp.where(sel, ref[...][:, None], 0), axis=0)

    cum_lo = pick(cum_lo_ref)
    ls = pick(lstarts_ref)
    rs = pick(rstarts_ref)
    rl = jnp.maximum(pick(rlens_ref), 1)

    w = t - cum_lo
    li = ls + w // rl
    ri = rs + w % rl
    valid = t < total_ref[0]
    li_ref[...] = jnp.where(valid, li, -1)
    ri_ref[...] = jnp.where(valid, ri, -1)


@functools.partial(jax.jit, static_argnames=("n_out", "interpret"))
def join_expand_kernel(
    cum_hi: jax.Array,  # (G,) int32 end offset of each group's output
    cum_lo: jax.Array,  # (G,) int32 start offset of each group's output
    lstarts: jax.Array,  # (G,) int32
    rstarts: jax.Array,  # (G,) int32
    rlens: jax.Array,  # (G,) int32
    base: jax.Array,  # (1,) int32 first output slot
    total: jax.Array,  # (1,) int32 total output slots of all groups
    *,
    n_out: int,  # slots to emit, a multiple of BLOCK
    interpret,
) -> Tuple[jax.Array, jax.Array]:
    """Device entry: (li, ri) for output slots [base, base + n_out)."""
    g = lstarts.shape[0]
    full = pl.BlockSpec((g,), lambda i: (0,))
    scalar = pl.BlockSpec((1,), lambda i: (0,))
    out = pl.BlockSpec((BLOCK,), lambda i: (i,))
    return pl.pallas_call(
        _kernel,
        grid=(n_out // BLOCK,),
        in_specs=[full, full, full, full, full, scalar, scalar],
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((n_out,), jnp.int32),
            jax.ShapeDtypeStruct((n_out,), jnp.int32),
        ],
        interpret=interpret,
    )(cum_hi, cum_lo, lstarts, rstarts, rlens, base, total)


def join_expand_pallas(
    lstarts,
    llens,  # unused by the kernel (cum encodes the products)
    rstarts,
    rlens,
    cum,  # (G+1,) cumulative output offsets
    base: int,
    count: int,
    *,
    interpret,
) -> Tuple[np.ndarray, np.ndarray]:
    del llens
    g = len(lstarts)
    assert g <= G_MAX, f"split probes beyond {G_MAX} groups in the wrapper"
    cum = np.asarray(cum, np.int32)
    total = int(cum[-1])
    # padded groups are empty and start at the total: no slot below the
    # total ever selects one
    li, ri = tiling.round_trip(
        join_expand_kernel,
        tiling.pad(cum[1:], BLOCK, total),
        tiling.pad(cum[:-1], BLOCK, total),
        tiling.pad(lstarts, BLOCK, 0),
        tiling.pad(rstarts, BLOCK, 0),
        tiling.pad(rlens, BLOCK, 1),
        np.asarray([base], np.int32),
        np.asarray([total], np.int32),
        n_out=tiling.bucket(count, BLOCK),
        interpret=interpret,
    )
    return li[:count], ri[:count]
