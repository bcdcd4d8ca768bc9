"""Pallas TPU kernel: segmented inclusive scan over sorted keys —
the vectorized core of streaming aggregation (paper §3.3).

out[i] = reduce(values over the maximal run of equal keys ending at i).
Within a block: log-step doubling scan (for sorted keys, key[i]==key[i-d]
implies the whole span is one run, so doubling is exact). Across blocks:
the TPU grid is sequential, so VMEM scratch tiles carry (last_key, last_acc)
— the batch-boundary carry merge the paper describes for associative
aggregates ('aggregate within a batch and merge the results across
batches').
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling

LANES = 128
ROWS = 8
BLOCK = ROWS * LANES  # rows of the flat input per grid step, as (ROWS, LANES)
_SENTINEL = np.iinfo(np.int32).min
_IDENT = {"sum": 0.0, "count": 0.0, "min": float("inf"), "max": float("-inf")}
_COMBINE = {
    "sum": jnp.add,
    "count": jnp.add,
    "min": jnp.minimum,
    "max": jnp.maximum,
}


def _shift(x, d: int, fill):
    """y.flat[i] = x.flat[i - d] over the row-major flattening of a
    (ROWS, LANES) tile, ``fill`` for i < d. Lane and sublane rotations
    only: the TPU concatenates no vectors at unaligned offsets."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    flat = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * LANES + lane
    if d % LANES == 0:
        y = pltpu.roll(x, d // LANES, 0)
    else:  # d < LANES: the first d lanes come from the previous row
        r = pltpu.roll(x, d, 1)
        y = jnp.where(lane >= d, r, pltpu.roll(r, 1, 0))
    return jnp.where(flat >= d, y, fill)


def _broadcast_last(x):
    """x.flat[-1] over the whole (ROWS, LANES) tile, through one-axis
    reductions and broadcasts: the TPU broadcasts no element across
    sublanes and lanes at once."""
    flat = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
    only = jnp.where(flat == BLOCK - 1, x, jnp.zeros_like(x))
    row = jnp.broadcast_to(jnp.sum(only, axis=1, keepdims=True), x.shape)
    return jnp.broadcast_to(jnp.sum(row, axis=0, keepdims=True), x.shape)


def _kernel(keys_ref, vals_ref, out_ref, carry_key, carry_val, *, op: str):
    b = pl.program_id(0)
    keys = keys_ref[...]  # (ROWS, LANES)
    out = vals_ref[...]
    combine = _COMBINE[op]
    ident = jnp.float32(_IDENT[op])

    # in-block segmented doubling scan
    d = 1
    while d < BLOCK:
        prev = _shift(out, d, ident)
        prev_key = _shift(keys, d, _SENTINEL)
        out = jnp.where(keys == prev_key, combine(out, prev), out)
        d *= 2

    # the carry is (last_key, last_acc) of the previous block, broadcast
    # over a whole tile: the TPU stores no scalars to VMEM
    @pl.when(b == 0)
    def _init():
        carry_key[...] = jnp.full(keys.shape, _SENTINEL, jnp.int32)
        carry_val[...] = jnp.full(out.shape, ident, jnp.float32)

    # merge the carried run (first run of this block only, keys are sorted)
    out = jnp.where(keys == carry_key[...], combine(out, carry_val[...]), out)

    out_ref[...] = out
    carry_key[...] = _broadcast_last(keys)
    carry_val[...] = _broadcast_last(out)


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def segment_scan_kernel(
    keys: jax.Array, values: jax.Array, *, op: str, interpret
) -> jax.Array:
    """Device entry over (R, LANES) int32 keys and float32 values, R a
    multiple of ROWS: the scan runs over their row-major flattening."""
    spec = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, op=op),
        grid=(keys.shape[0] // ROWS,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(keys.shape, jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((ROWS, LANES), jnp.int32),
            pltpu.VMEM((ROWS, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(keys, values)


def segment_scan_pallas(keys, values, op: str = "sum", *, interpret
                        ) -> np.ndarray:
    """(N,) float32 segmented inclusive scan; padding rows carry a key
    below every real one and the identity value."""
    n = len(keys)
    out = tiling.round_trip(
        segment_scan_kernel,
        tiling.pad(keys, BLOCK, _SENTINEL + 1).reshape(-1, LANES),
        tiling.pad(values, BLOCK, _IDENT[op], np.float32).reshape(-1, LANES),
        op=op,
        interpret=interpret,
    )
    return out.reshape(-1)[:n]
