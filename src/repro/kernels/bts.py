"""Pallas TPU kernels: block-tridiagonal solve (SaP forward/backward sweeps).

Two kernels, each on grid ``(P // pt, M)`` with a VMEM carry:

  forward:   y_0 = b_0,          y_j = b_j - L_j y_{j-1}
  backward:  x_{M-1} = Sinv y,   x_j = Sinv_j (y_j - F_j x_{j+1})

A grid step advances a tile of ``pt`` independent partition chains by one
block row: the blocks are ``(pt, 1, K, K)`` and ``(pt, 1, K, R)``, the
carry ``(pt, K, R)``, and the products one batched ``mm`` over the tile
(f32 at HIGHEST; on a TPU v5e an f32 multiply-and-lane-sum on the VPU was
within 5 % of it at R = 1 and cannot hold R = K).
``pt`` is read from the shape (:func:`partition_tile`), so a fleet of
small blocks takes a few grid steps of real work instead of one tiny
step per block.  The backward kernel runs the same ascending grid but its
BlockSpec index_map reverses the block-row axis, so the sequential VMEM
carry walks the partitions bottom-up.  Multiple right-hand sides (R
columns) are handled in one pass -- the spike computation (paper Sec.
2.1) is just this solve with R = K columns.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.block_lu import mm

# VMEM a grid step may take: under the 16 MiB that Mosaic scopes to a
# kernel, with room for its own scratch.
VMEM_BUDGET = 14 * 2**20


def _vmem_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes of one (rows, cols) VMEM buffer in (8 * packing, 128) tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * (4 // itemsize)
    return (
        math.ceil(rows / sublanes) * sublanes * math.ceil(cols / 128) * 128
        * itemsize
    )


def partition_tile(p: int, k: int, r: int, dtype) -> int:
    """Partition chains a grid step solves: the largest divisor of P whose
    working set fits :data:`VMEM_BUDGET`.

    The backward kernel bounds it.  Per chain: the Sinv, F, y and x blocks,
    each double-buffered; the f32 carry and the step's f32 vectors (y, the
    product, y - F x, x); and f32 copies of Sinv and F when they are stored
    narrower.
    """
    f32 = jnp.float32
    mat, vec = _vmem_bytes(k, k, dtype), _vmem_bytes(k, r, dtype)
    chain = 2 * (2 * mat + 2 * vec) + 5 * _vmem_bytes(k, r, f32)
    if jnp.dtype(dtype) != f32:
        chain += 2 * _vmem_bytes(k, k, f32)
    return max(
        (t for t in range(1, p + 1) if p % t == 0 and t * chain <= VMEM_BUDGET),
        default=1,
    )


def _fwd_kernel(l_ref, b_ref, y_ref, carry):
    j = pl.program_id(1)
    b = b_ref[:, 0].astype(jnp.float32)

    @pl.when(j == 0)
    def _first():
        carry[...] = b
        y_ref[:, 0] = b.astype(y_ref.dtype)

    @pl.when(j > 0)
    def _rest():
        l = l_ref[:, 0].astype(jnp.float32)
        y = b - mm(l, carry[...])
        carry[...] = y
        y_ref[:, 0] = y.astype(y_ref.dtype)


def _bwd_kernel(sinv_ref, f_ref, y_ref, x_ref, carry):
    jr = pl.program_id(1)  # 0 .. M-1, walking bottom-up via index_map
    sinv = sinv_ref[:, 0].astype(jnp.float32)
    y = y_ref[:, 0].astype(jnp.float32)

    @pl.when(jr == 0)
    def _first():
        x = mm(sinv, y)
        carry[...] = x
        x_ref[:, 0] = x.astype(x_ref.dtype)

    @pl.when(jr > 0)
    def _rest():
        f = f_ref[:, 0].astype(jnp.float32)
        rhs = y - mm(f, carry[...])
        x = mm(sinv, rhs)
        carry[...] = x
        x_ref[:, 0] = x.astype(x_ref.dtype)


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
    pt = partition_tile(p, k, r, sinv.dtype)
    blk_m = (pt, 1, k, k)
    blk_v = (pt, 1, k, r)
    fwd_spec_m = pl.BlockSpec(blk_m, lambda i, j: (i, j, 0, 0))
    fwd_spec_v = pl.BlockSpec(blk_v, lambda i, j: (i, j, 0, 0))

    y = pl.pallas_call(
        _fwd_kernel,
        grid=(p // pt, m),
        in_specs=[fwd_spec_m, fwd_spec_v],
        out_specs=fwd_spec_v,
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((pt, k, r), jnp.float32)],
        interpret=interpret,
        name="sap_bts_forward",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(l, b)

    rev_m = pl.BlockSpec(blk_m, lambda i, j: (i, m - 1 - j, 0, 0))
    rev_v = pl.BlockSpec(blk_v, lambda i, j: (i, m - 1 - j, 0, 0))
    x = pl.pallas_call(
        _bwd_kernel,
        grid=(p // pt, m),
        in_specs=[rev_m, rev_m, rev_v],
        out_specs=rev_v,
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((pt, k, r), jnp.float32)],
        interpret=interpret,
        name="sap_bts_backward",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(sinv, f, y)
    return x
