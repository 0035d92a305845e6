"""Pallas TPU kernels: block-tridiagonal solve (SaP forward/backward sweeps).

Two kernels, each on grid ``(P, M)`` with a VMEM carry:

  forward:   y_0 = b_0,          y_j = b_j - L_j y_{j-1}
  backward:  x_{M-1} = Sinv y,   x_j = Sinv_j (y_j - F_j x_{j+1})

The backward kernel runs the same ascending grid but its BlockSpec
index_map reverses the block-row axis, so the sequential VMEM carry walks
the partition bottom-up.  Multiple right-hand sides (R columns) are
handled in one pass -- the spike computation (paper Sec. 2.1) is just this
solve with R = K columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.block_lu import mm


def _fwd_kernel(l_ref, b_ref, y_ref, carry):
    j = pl.program_id(1)
    b = b_ref[0, 0].astype(jnp.float32)

    @pl.when(j == 0)
    def _first():
        carry[...] = b
        y_ref[0, 0] = b.astype(y_ref.dtype)

    @pl.when(j > 0)
    def _rest():
        l = l_ref[0, 0].astype(jnp.float32)
        y = b - mm(l, carry[...])
        carry[...] = y
        y_ref[0, 0] = y.astype(y_ref.dtype)


def _bwd_kernel(sinv_ref, f_ref, y_ref, x_ref, carry):
    jr = pl.program_id(1)  # 0 .. M-1, walking bottom-up via index_map
    sinv = sinv_ref[0, 0].astype(jnp.float32)
    y = y_ref[0, 0].astype(jnp.float32)

    @pl.when(jr == 0)
    def _first():
        x = mm(sinv, y)
        carry[...] = x
        x_ref[0, 0] = x.astype(x_ref.dtype)

    @pl.when(jr > 0)
    def _rest():
        f = f_ref[0, 0].astype(jnp.float32)
        rhs = y - mm(f, carry[...])
        x = mm(sinv, rhs)
        carry[...] = x
        x_ref[0, 0] = x.astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bts_pallas(
    sinv: jax.Array,
    l: jax.Array,
    f: jax.Array,
    b: jax.Array,
    *,
    interpret: bool,
):
    """Solve D x = b for all partitions.

    sinv/l/f: (P, M, K, K);  b: (P, M, K, R)  ->  x: (P, M, K, R).
    """
    p, m, k, _ = sinv.shape
    r = b.shape[-1]
    blk_m = (1, 1, k, k)
    blk_v = (1, 1, k, r)
    fwd_spec_m = pl.BlockSpec(blk_m, lambda i, j: (i, j, 0, 0))
    fwd_spec_v = pl.BlockSpec(blk_v, lambda i, j: (i, j, 0, 0))

    y = pl.pallas_call(
        _fwd_kernel,
        grid=(p, m),
        in_specs=[fwd_spec_m, fwd_spec_v],
        out_specs=fwd_spec_v,
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((k, r), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(l, b)

    rev_m = pl.BlockSpec(blk_m, lambda i, j: (i, m - 1 - j, 0, 0))
    rev_v = pl.BlockSpec(blk_v, lambda i, j: (i, m - 1 - j, 0, 0))
    x = pl.pallas_call(
        _bwd_kernel,
        grid=(p, m),
        in_specs=[rev_m, rev_m, rev_v],
        out_specs=rev_v,
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((k, r), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(sinv, f, y)
    return x
