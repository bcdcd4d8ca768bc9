"""Pallas TPU kernels: blocked bloom filter build + membership probe.

The sideways-information-passing prefilter (DESIGN.md §12): a hash/merge
join's build side is summarized as one uint32 word per block, two bits per
key, and probe-side scans test membership batch-at-a-time before the join
ever sees the rows. Both kernels are gather/scatter-free: addressing is a
one-hot comparison matrix against the word tile, so they run on the same
(block, tile) sequential-grid accumulation pattern as frontier_dedup.

  * build — scatter-OR decomposed per bit plane: a one-hot (word × key)
    matmul against the key's 32 bit indicators counts how many keys set
    each (word, bit); any nonzero count sets the bit. OR across key blocks
    accumulates in-place in VMEM (output revisiting).
  * probe — each query gathers its word via a one-hot sum over word tiles
    (exactly one tile matches), then checks both bits in the jitted
    epilogue.

Address computation must match vecops.bloom_hash bit for bit — the parity
sweeps in tests/test_sip.py hold all three backends to identical words.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import tiling

K_BLOCK = 1024  # build keys per grid step
Q_BLOCK = 1024  # probe queries per grid step
W_TILE = 1024  # filter words resident per grid step
_PAD = np.iinfo(np.int32).min
_MULT1 = np.uint32(0x9E3779B1)
_MULT2 = np.uint32(0x85EBCA6B)


def _hash(keys, n_words: int):
    u = keys.astype(jnp.uint32)
    h1 = u * _MULT1
    h2 = u * _MULT2
    word = ((h1 >> np.uint32(18)) & np.uint32(n_words - 1)).astype(jnp.int32)
    bits = (jnp.uint32(1) << (h1 & np.uint32(31))) | (
        jnp.uint32(1) << ((h2 >> np.uint32(13)) & np.uint32(31))
    )
    return word, bits


def _build_kernel(keys_ref, out_ref, *, n_words: int):
    i = pl.program_id(0)  # word tile
    j = pl.program_id(1)  # key block
    keys = keys_ref[...]  # (K_BLOCK,)
    word, bits = _hash(keys, n_words)
    rel = word - i * W_TILE
    sel = (keys != _PAD) & (rel >= 0) & (rel < W_TILE)
    rel = jnp.where(sel, rel, 0)
    # (K_BLOCK, 32) bit indicators, zeroed for padding/out-of-tile keys
    planes = (
        (bits[:, None] >> jnp.arange(32, dtype=jnp.uint32)[None, :])
        & jnp.uint32(1)
    ).astype(jnp.int32) * sel.astype(jnp.int32)[:, None]
    onehot = (
        jax.lax.iota(jnp.int32, W_TILE)[:, None] == rel[None, :]
    )  # (W_TILE, K_BLOCK)
    # (W_TILE, 32) keys setting each bit: 0/1 operands in bf16 on the MXU
    # (it multiplies no int32), counts exact in the float32 accumulator
    counts = jnp.dot(
        jnp.where(onehot, 1.0, 0.0).astype(jnp.bfloat16),
        planes.astype(jnp.float32).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    # OR of distinct single-bit words == their int32 sum (no carries; bit
    # 31 wraps to INT32_MIN): the TPU reduces no unsigned integers
    weights = jnp.left_shift(jnp.int32(1), jax.lax.iota(jnp.int32, 32))
    tile_or = jnp.sum(jnp.where(counts > 0, weights[None, :], 0), axis=1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = tile_or

    @pl.when(j != 0)
    def _acc():
        out_ref[...] = out_ref[...] | tile_or


@functools.partial(jax.jit, static_argnames=("n_words", "interpret"))
def bloom_build_kernel(keys: jax.Array, *, n_words: int, interpret
                       ) -> jax.Array:
    """Device entry over K_BLOCK-aligned padded keys: (W,) int32 bit
    patterns of the filter words, W = n_words rounded up to W_TILE."""
    w_pad = tiling.bucket(n_words, W_TILE)
    return pl.pallas_call(
        functools.partial(_build_kernel, n_words=n_words),
        grid=(w_pad // W_TILE, keys.shape[0] // K_BLOCK),
        in_specs=[pl.BlockSpec((K_BLOCK,), lambda i, j: (j,))],
        out_specs=pl.BlockSpec((W_TILE,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((w_pad,), jnp.int32),
        interpret=interpret,
    )(keys)


def bloom_build_pallas(keys, n_words: int, *, interpret) -> np.ndarray:
    """(n_words,) uint32 filter words — see vecops.bloom_build."""
    assert n_words & (n_words - 1) == 0, "n_words must be a power of two"
    words = tiling.round_trip(
        bloom_build_kernel, tiling.pad(keys, K_BLOCK, _PAD), n_words=n_words,
        interpret=interpret,
    )
    return words[:n_words].view(np.uint32)


def _probe_kernel(words_ref, q_ref, out_ref, *, n_words: int):
    j = pl.program_id(1)  # word tile
    words = words_ref[...]  # (W_TILE,) int32 bit patterns
    q = q_ref[...]  # (Q_BLOCK,)
    word, _ = _hash(q, n_words)
    rel = word - j * W_TILE
    sel = (rel >= 0) & (rel < W_TILE)
    rel = jnp.where(sel, rel, 0)
    onehot = (
        jax.lax.iota(jnp.int32, W_TILE)[:, None] == rel[None, :]
    ) & sel[None, :]
    vals = jnp.sum(jnp.where(onehot, words[:, None], 0), axis=0)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = vals

    @pl.when(j != 0)
    def _acc():
        out_ref[...] = out_ref[...] + vals  # exactly one tile is nonzero


@functools.partial(jax.jit, static_argnames=("n_words", "interpret"))
def bloom_probe_kernel(words: jax.Array, queries: jax.Array, *, n_words: int,
                       interpret) -> jax.Array:
    """Device entry: W_TILE-aligned int32 word patterns and Q_BLOCK-aligned
    queries → (Q,) bool membership."""
    gathered = pl.pallas_call(
        functools.partial(_probe_kernel, n_words=n_words),
        grid=(queries.shape[0] // Q_BLOCK, words.shape[0] // W_TILE),
        in_specs=[
            pl.BlockSpec((W_TILE,), lambda i, j: (j,)),
            pl.BlockSpec((Q_BLOCK,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((Q_BLOCK,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct(queries.shape, jnp.int32),
        interpret=interpret,
    )(words, queries)
    _, bits = _hash(queries, n_words)
    bits = jax.lax.bitcast_convert_type(bits, jnp.int32)
    return (gathered & bits) == bits


def bloom_probe_pallas(words, queries, *, interpret) -> np.ndarray:
    """(C,) bool membership mask — see vecops.bloom_probe."""
    n_words = len(words)
    c = len(queries)
    words = np.asarray(words, np.uint32).view(np.int32)
    mask = tiling.round_trip(
        bloom_probe_kernel,
        tiling.pad(words, W_TILE, 0),
        tiling.pad(queries, Q_BLOCK, _PAD),
        n_words=n_words,
        interpret=interpret,
    )
    return mask[:c]
