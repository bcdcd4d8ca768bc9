"""Compile every Pallas kernel for a TPU v5e, at the widths the engine
feeds it, without a chip attached.

Each case lowers a kernel's jitted device entry with ``interpret=False``
for one chip of a described ``v5e:2x2`` topology and compiles it: the
TPU compiler refuses what interpret mode hides (tile-misaligned blocks,
scalar stores to VMEM, unsigned reductions, too much VMEM). Widths: a
batch of 4096 rows, build sides and visited sets of 2^20 rows, bloom
filters of 2^16 words. Nothing runs, so these tests say nothing about
results or times.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import algebra as A
from repro.core.dictionary import Dictionary
from repro.core.exprs.compiler import compile_expr
from repro.kernels import (
    bloom_filter,
    expr_eval,
    frontier_dedup,
    gather_emit,
    hash_join,
    join_expand,
    radix_partition,
    segment_reduce,
    sorted_search,
)

BATCH = 4096
BUILD = 1 << 20
WORDS = 1 << 16


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on one chip of a described (not attached) v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device can be written to the persistent
    # cache but not read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _shapes(one_chip, *shapes):
    return [
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes
    ]


def _compile(fn, args, **static):
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


i32, f32 = jnp.int32, jnp.float32


@pytest.mark.parametrize("left", [True, False])
def test_sorted_search(one_chip, left):
    args = _shapes(one_chip, ((BUILD,), i32), ((BATCH,), i32))
    _compile(sorted_search.sorted_search_kernel, args, left=left)


def test_hash_probe(one_chip):
    args = _shapes(one_chip, *[((BUILD,), i32)] * 3, *[((BATCH,), i32)] * 3)
    _compile(hash_join.hash_probe_kernel, args)


@pytest.mark.parametrize("groups", [join_expand.BLOCK, join_expand.G_MAX])
def test_join_expand(one_chip, groups):
    args = _shapes(one_chip, *[((groups,), i32)] * 5, ((1,), i32), ((1,), i32))
    _compile(join_expand.join_expand_kernel, args, n_out=BATCH)


@pytest.mark.parametrize("n_pairs", [0, 1])
def test_gather_emit(one_chip, n_pairs):
    args = _shapes(one_chip, ((3, BUILD), i32), ((2, BUILD), i32),
                   ((BATCH,), i32), ((BATCH,), i32))
    _compile(gather_emit.gather_emit_kernel, args, n_pairs=n_pairs)


def test_frontier_dedup(one_chip):
    args = _shapes(one_chip, *[((BUILD,), i32)] * 2, *[((BATCH,), i32)] * 4)
    _compile(frontier_dedup.frontier_dedup_kernel, args)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_scan(one_chip, op):
    tile = (BATCH // segment_reduce.LANES, segment_reduce.LANES)
    args = _shapes(one_chip, (tile, i32), (tile, f32))
    _compile(segment_reduce.segment_scan_kernel, args, op=op)


@pytest.mark.parametrize("n_parts", [1, 1024])
def test_radix_partition(one_chip, n_parts):
    args = _shapes(one_chip, ((BUILD,), i32))
    _compile(radix_partition.radix_partition_kernel, args, n_parts=n_parts)


@pytest.mark.parametrize("n_words", [1, WORDS])
def test_bloom_build(one_chip, n_words):
    args = _shapes(one_chip, ((BUILD,), i32))
    _compile(bloom_filter.bloom_build_kernel, args, n_words=n_words)


def test_bloom_probe(one_chip):
    args = _shapes(one_chip, ((WORDS,), i32), ((BATCH,), i32))
    _compile(bloom_filter.bloom_probe_kernel, args, n_words=WORDS)


def test_expr_eval(one_chip):
    d = Dictionary()
    for v in range(8):
        d.encode(int(v))
    # numeric comparison (float plane) AND code equality (int plane)
    expr = A.And((A.Cmp("<", A.VarRef(0), A.Lit(5)),
                  A.Cmp("=", A.VarRef(1), A.VarRef(2))))
    prog = compile_expr(expr, d, "mask")
    n = expr_eval.BLOCK
    args = _shapes(one_chip, ((max(prog.n_icols, 1), n), i32),
                   ((max(prog.n_fcols, 1), n), f32))
    _compile(expr_eval.expr_eval_kernel, args, prog=prog)
