"""Pallas TPU kernel: fused expression-VM evaluation (DESIGN.md §9.3).

One kernel dispatch per batch evaluates an *entire* compiled expression
program — arithmetic, comparisons, three-valued logic, IF/COALESCE and the
pre-broadcast dictionary-domain predicate columns — over a block of the
referenced columns only. The program is a static argument: the shared
interpreter (core/exprs/vm._interp) unrolls instruction-by-instruction at
trace time, so each hot expression compiles to its own fused kernel whose
register file lives entirely in VMEM. This generalizes and replaces the
old conjunction-only filter_eval kernel: any FILTER/BIND/left-join
condition the compiler can lower now runs in one dispatch.

Inputs: icols (KI, N) int32 — dictionary-code columns then trinary
predicate columns; fcols (KF, N) float32 — numeric side-array decodes
(NaN = non-numeric/NULL). Outputs: (value float32, error bool) for the
program's output register; the FILTER mask is value != 0 & ~error.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.exprs.bytecode import ExprProgram
from repro.core.exprs.vm import _interp
from repro.kernels import tiling

# wide blocks: the register file is a handful of (BLOCK,) vectors, so VMEM
# stays small even at 8k lanes, and fewer grid steps amortize dispatch
# (and, on CPU, interpret-mode) overhead across more rows
BLOCK = 8192


def _kernel(icols_ref, fcols_ref, val_ref, err_ref, *, prog: ExprProgram):
    val, err = _interp(jnp, prog, icols_ref[...], fcols_ref[...], jnp.float32)
    val_ref[...] = val
    err_ref[...] = err


@functools.partial(jax.jit, static_argnames=("prog", "interpret"))
def expr_eval_kernel(
    icols: jax.Array,
    fcols: jax.Array,
    *,
    prog: ExprProgram,
    interpret,
):
    """Device entry over BLOCK-aligned padded columns: (value, error)."""
    ki, n = icols.shape
    kf = fcols.shape[0]
    out = pl.BlockSpec((BLOCK,), lambda i: (i,))
    return pl.pallas_call(
        functools.partial(_kernel, prog=prog),
        grid=(n // BLOCK,),
        in_specs=[
            pl.BlockSpec((ki, BLOCK), lambda i: (0, i)),
            pl.BlockSpec((kf, BLOCK), lambda i: (0, i)),
        ],
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
        ],
        interpret=interpret,
    )(icols, fcols)


def expr_eval_pallas(icols, fcols, prog: ExprProgram, *, interpret):
    n = icols.shape[1]
    # padding rows: NULL codes / NaN values — they evaluate to errors that
    # the final slice drops
    val, err = tiling.round_trip(
        expr_eval_kernel,
        tiling.pad(icols, BLOCK, -1),
        tiling.pad(fcols, BLOCK, np.nan, np.float32),
        prog=prog,
        interpret=interpret,
    )
    return val[:n], err[:n]
