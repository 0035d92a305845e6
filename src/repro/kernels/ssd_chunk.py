"""Pallas TPU kernel: chunked Mamba-2 SSD recurrence.

Same split-and-parallelize structure as the WKV kernel, but Mamba-2's
decay is a *scalar per head per step*, so the intra-chunk term factors
into pure matmuls -- this kernel is MXU-bound:

    G = (C B^T) * e^{Lcum_t - Lcum_s}   masked s <= t      (C, C)
    y = e^{Lcum} * (C @ S_in) + G @ X                      (C, P)
    S_out = e^{Llast} S_in + (B * e^{Llast - Lcum})^T X    (N, P)

Grid ``(B*H, T/C)``, chunk axis sequential, state carried in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, la_ref, s0_ref, y_ref, sout_ref, s, *, chunk):
    nc = pl.program_id(1)
    c = chunk

    @pl.when(nc == 0)
    def _init():
        s[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)  # (C, P)
    bmat = b_ref[0].astype(jnp.float32)  # (C, N)
    cmat = c_ref[0].astype(jnp.float32)  # (C, N)
    la = la_ref[0].astype(jnp.float32)  # (C,)

    lcum = jnp.cumsum(la)  # (C,)
    y_inter = jnp.exp(lcum)[:, None] * jnp.dot(
        cmat, s[...], preferred_element_type=jnp.float32
    )

    diff = lcum[:, None] - lcum[None, :]
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    decay = jnp.where(ti >= si, jnp.exp(diff), 0.0)
    g = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32) * decay
    y = y_inter + jnp.dot(g, x, preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    llast = lcum[-1]
    bd = bmat * jnp.exp(llast - lcum)[:, None]
    s[...] = jnp.exp(llast) * s[...] + jnp.dot(
        bd.T, x, preferred_element_type=jnp.float32
    )

    @pl.when(nc == pl.num_programs(1) - 1)
    def _flush():
        sout_ref[0] = s[...].astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(
    x: jax.Array,  # (BH, T, P)
    b: jax.Array,  # (BH, T, N)
    c: jax.Array,  # (BH, T, N)
    loga: jax.Array,  # (BH, T)
    state: jax.Array,  # (BH, N, P)
    chunk: int = 64,
    *,
    interpret: bool,
):
    bh, t, p = x.shape
    n = b.shape[-1]
    assert t % chunk == 0, f"T={t} not divisible by chunk={chunk}"
    ncs = t // chunk
    seq_p = pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0))
    seq_n = pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0))
    seq_s = pl.BlockSpec((1, chunk), lambda i, j: (i, j))
    st = pl.BlockSpec((1, n, p), lambda i, j: (i, 0, 0))
    y, s_out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(bh, ncs),
        in_specs=[seq_p, seq_n, seq_n, seq_s, st],
        out_specs=[seq_p, st],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), state.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(x, b, c, loga, state)
    return y, s_out
