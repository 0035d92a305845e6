"""Pallas TPU megakernel: fused block-LU factor + spike extraction.

One ``pallas_call`` grid over ``(P, M)`` replaces the btf -> UL-btf ->
bts kernel *sequence* of the SaP factor stage (paper Sec. 3.1: SaP::GPU
factors each diagonal sub-block and extracts its spikes in a single
on-chip pass).  Four K x K carries live in VMEM across the sequential
``j`` axis:

  * ``c_lu`` -- the LU recurrence carry ``inv(S_{j-1})`` (as in
    ``kernels/btf.py``); ``sinv_j`` / ``l_j`` stream out as usual.
  * ``c_ul`` -- the SAME recurrence on the *reversed* chain
    (``flip_block_tridiag``), i.e. the UL factorization.  Only the carry
    is kept: no UL factors are ever materialized in HBM, which is the
    bulk of the HBM traffic the kernel sequence pays.
  * ``c_w``  -- the left-spike RHS swept forward through LU:
    ``y_0 = C_i``, ``y_j = -l_j y_{j-1}`` (rhs is zero past block 0), so
    ``w_bot = sinv_{M-1} y_{M-1}`` without a backward substitution.
  * ``c_v``  -- the right-spike RHS swept forward through UL:
    ``yr_0 = flip(B_i)``, ``yr_j = -l^{UL}_j yr_{j-1}``, so
    ``v_top = flip(sinv^{UL}_{M-1} yr_{M-1})``.

At ``j = M-1`` the four spike corner blocks (v_bot / v_top / w_top /
w_bot) are emitted into constant-index output blocks (flushed once at the
end of each partition's sweep).  The reversed-chain blocks are read
through reversed BlockSpec index maps (the ``kernels/bts.py`` backward
idiom) and flipped in VMEM, so no reversed copy of the chain exists in
HBM either.  Mosaic has no in-kernel reversal (``rev``), so a flip is a
product with the exchange matrix J (ones on the anti-diagonal).  Like
every matmul of the solver it runs at ``precision=HIGHEST``
(:func:`repro.core.block_lu.mm`): the TPU's DEFAULT f32 matmul rounds
its operands through bf16, HIGHEST carries all 24 bits, and a product
with a permutation matrix is then an exact copy.

Oracle: :func:`repro.core.block_lu.fused_factor_spike_padded_ref`, the
op-for-op scan formulation -- interpret mode matches it bit-for-bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.block_lu import DEFAULT_BOOST, gj_inverse, mm


def _exchange(k: int) -> jax.Array:
    """The (K, K) exchange matrix J: ``J @ x`` reverses the rows of x."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    return (rows + cols == k - 1).astype(jnp.float32)


def _fused_kernel(
    d_ref, e_ref, f_prev_ref, d_rev_ref, f_rev_ref, e_revp1_ref,
    bq_ref, cq_ref,
    sinv_ref, l_ref, vb_ref, vt_ref, wt_ref, wb_ref,
    c_lu, c_ul, c_w, c_v,
    *, boost_eps,
):
    j = pl.program_id(1)
    m = pl.num_programs(1)

    d = d_ref[0, 0].astype(jnp.float32)
    jx = _exchange(d.shape[0])
    fliprows = lambda x: mm(jx, x)
    flip2 = lambda x: mm(mm(jx, x), jx)
    # reversed-chain blocks, flipped in VMEM (flip_block_tridiag values)
    d_r = flip2(d_rev_ref[0, 0].astype(jnp.float32))
    bq = bq_ref[0].astype(jnp.float32)
    cq = cq_ref[0].astype(jnp.float32)

    @pl.when(j == 0)
    def _first():
        sinv = gj_inverse(d, boost_eps)
        c_lu[...] = sinv
        sinv_ref[0, 0] = sinv.astype(sinv_ref.dtype)
        l_ref[0, 0] = jnp.zeros_like(d).astype(l_ref.dtype)
        c_ul[...] = gj_inverse(d_r, boost_eps)
        c_w[...] = cq
        c_v[...] = fliprows(bq)

    @pl.when(j > 0)
    def _rest():
        e = e_ref[0, 0].astype(jnp.float32)
        f_prev = f_prev_ref[0, 0].astype(jnp.float32)
        lj = mm(e, c_lu[...])
        sj = d - mm(lj, f_prev)
        sinv = gj_inverse(sj, boost_eps)
        c_lu[...] = sinv
        sinv_ref[0, 0] = sinv.astype(sinv_ref.dtype)
        l_ref[0, 0] = lj.astype(l_ref.dtype)
        c_w[...] = -mm(lj, c_w[...])

        e_r = flip2(f_rev_ref[0, 0].astype(jnp.float32))
        f_r_prev = flip2(e_revp1_ref[0, 0].astype(jnp.float32))
        l_ul = mm(e_r, c_ul[...])
        s_ul = d_r - mm(l_ul, f_r_prev)
        c_ul[...] = gj_inverse(s_ul, boost_eps)
        c_v[...] = -mm(l_ul, c_v[...])

    @pl.when(j == m - 1)
    def _emit():
        sinv = c_lu[...]
        sinv_ul = c_ul[...]
        vb_ref[0] = mm(sinv, bq).astype(vb_ref.dtype)
        wb_ref[0] = mm(sinv, c_w[...]).astype(wb_ref.dtype)
        wt_ref[0] = fliprows(mm(sinv_ul, fliprows(cq))).astype(wt_ref.dtype)
        vt_ref[0] = fliprows(mm(sinv_ul, c_v[...])).astype(vt_ref.dtype)


@functools.partial(jax.jit, static_argnames=("boost_eps", "interpret"))
def fused_factor_spike_pallas(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    bq: jax.Array,
    cq: jax.Array,
    boost_eps: float = DEFAULT_BOOST,
    *,
    interpret: bool,
):
    """Fused factor + spike corners for all partitions.

    d/e/f: (P, M, K, K); bq/cq: (P, K, K) per-partition couplings (see
    :func:`repro.core.block_lu.pad_couplings`).  Returns
    ``(sinv, l, vb, vt, wt, wb)``: the LU factors (P, M, K, K) and the
    four spike corner blocks (P, K, K).
    """
    p, m, k, _ = d.shape
    blk = (1, 1, k, k)
    spec_j = pl.BlockSpec(blk, lambda i, j: (i, j, 0, 0))
    spec_jm1 = pl.BlockSpec(blk, lambda i, j: (i, jnp.maximum(j - 1, 0), 0, 0))
    spec_rev = pl.BlockSpec(blk, lambda i, j: (i, m - 1 - j, 0, 0))
    # f_r[j-1] = flip2(e[M-j]); clamp the unused j = 0 slot into range
    spec_revp1 = pl.BlockSpec(
        blk, lambda i, j: (i, jnp.minimum(m - j, m - 1), 0, 0)
    )
    blk_c = (1, k, k)
    spec_c = pl.BlockSpec(blk_c, lambda i, j: (i, 0, 0))
    out_shape = [
        jax.ShapeDtypeStruct(d.shape, d.dtype),  # sinv
        jax.ShapeDtypeStruct(d.shape, d.dtype),  # l
        jax.ShapeDtypeStruct((p, k, k), d.dtype),  # v_bot
        jax.ShapeDtypeStruct((p, k, k), d.dtype),  # v_top
        jax.ShapeDtypeStruct((p, k, k), d.dtype),  # w_top
        jax.ShapeDtypeStruct((p, k, k), d.dtype),  # w_bot
    ]
    return pl.pallas_call(
        functools.partial(_fused_kernel, boost_eps=boost_eps),
        grid=(p, m),
        in_specs=[
            spec_j, spec_j, spec_jm1, spec_rev, spec_rev, spec_revp1,
            spec_c, spec_c,
        ],
        out_specs=[spec_j, spec_j, spec_c, spec_c, spec_c, spec_c],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((k, k), jnp.float32),  # c_lu
            pltpu.VMEM((k, k), jnp.float32),  # c_ul
            pltpu.VMEM((k, k), jnp.float32),  # c_w
            pltpu.VMEM((k, k), jnp.float32),  # c_v
        ],
        interpret=interpret,
        name="sap_fused_factor_spike",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(d, e, f, d, f, e, bq, cq)
