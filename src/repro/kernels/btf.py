"""Pallas TPU kernel: block-tridiagonal factorization (SaP T_LU stage).

TPU adaptation of the paper's dense-banded LU (Sec. 3.1).  The paper's
GPU implementation slides a (K+1)x(K+1) scalar window with one thread per
matrix entry; on TPU we instead factor the band as a block-tridiagonal
chain of (K x K) blocks so each step is an MXU matmul:

    S_0 = D_0,   L_j = E_j inv(S_{j-1}),   S_j = D_j - L_j F_{j-1}

Grid layout: ``(P, M)`` -- partitions on the (parallel) first axis, block
rows on the (sequential, innermost) second axis.  The running inverse
``inv(S_{j-1})`` lives in a VMEM scratch buffer that persists across the
sequential ``j`` steps; each grid step streams one (K, K) block of D / E /
F from HBM into VMEM via the BlockSpecs, exactly the "window of focus"
pattern of the paper mapped onto the TPU memory hierarchy.

Pivoting is replaced by pivot boosting inside the Gauss-Jordan inversion
(paper Sec. 2.2), which keeps the kernel branch-free -- the property that
made the original algorithm GPU-friendly makes it MXU/VPU-friendly here.
Structurally zero pivot rows (identity padding from shape bucketing) are
exempt from boosting and take pivot 1 instead -- see
:func:`repro.core.block_lu.gj_inverse`, shared by kernel and oracle, so
padded embeddings stay exactly blkdiag(A, I) in both paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.block_lu import DEFAULT_BOOST, gj_inverse, mm


def _btf_kernel(d_ref, e_ref, f_prev_ref, sinv_ref, l_ref, carry, *, boost_eps):
    j = pl.program_id(1)

    d = d_ref[0, 0].astype(jnp.float32)

    @pl.when(j == 0)
    def _first():
        sinv = gj_inverse(d, boost_eps)
        carry[...] = sinv
        sinv_ref[0, 0] = sinv.astype(sinv_ref.dtype)
        l_ref[0, 0] = jnp.zeros_like(d).astype(l_ref.dtype)

    @pl.when(j > 0)
    def _rest():
        e = e_ref[0, 0].astype(jnp.float32)
        f_prev = f_prev_ref[0, 0].astype(jnp.float32)
        lj = mm(e, carry[...])
        sj = d - mm(lj, f_prev)
        sinv = gj_inverse(sj, boost_eps)
        carry[...] = sinv
        sinv_ref[0, 0] = sinv.astype(sinv_ref.dtype)
        l_ref[0, 0] = lj.astype(l_ref.dtype)


@functools.partial(jax.jit, static_argnames=("boost_eps", "interpret"))
def btf_pallas(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    boost_eps: float = DEFAULT_BOOST,
    *,
    interpret: bool,
):
    """Factor all partitions.  d/e/f: (P, M, K, K) -> (sinv, l) same shape."""
    p, m, k, _ = d.shape
    blk = (1, 1, k, k)
    spec_j = pl.BlockSpec(blk, lambda i, j: (i, j, 0, 0))
    spec_jm1 = pl.BlockSpec(blk, lambda i, j: (i, jnp.maximum(j - 1, 0), 0, 0))
    out_shape = [
        jax.ShapeDtypeStruct(d.shape, d.dtype),  # sinv
        jax.ShapeDtypeStruct(d.shape, d.dtype),  # l
    ]
    return pl.pallas_call(
        functools.partial(_btf_kernel, boost_eps=boost_eps),
        grid=(p, m),
        in_specs=[spec_j, spec_j, spec_jm1],
        out_specs=[spec_j, spec_j],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((k, k), jnp.float32)],
        interpret=interpret,
        name="sap_btf",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(d, e, f)
