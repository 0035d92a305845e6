"""Pallas TPU kernel: chunked RWKV6 WKV recurrence (the "SaP-scan").

The WKV recurrence  S_t = diag(w_t) S_{t-1} + k_t v_t^T  is the solve of a
block lower-*bidiagonal* linear system in the states S_t.  Applying the
paper's split-and-parallelize idea along the *sequence* axis gives the
chunked algorithm implemented here: each chunk is a local solve (the
intra-chunk term), and the inter-chunk coupling -- the paper's spike /
reduced system, which for a lower-triangular system collapses to a carry
chain -- flows through a VMEM scratch state.

Grid: ``(B*H, T/C)`` with the chunk axis sequential.  Per chunk:

    Lcum  = cumsum(log w)                       (C, D), <= 0
    o_t   = (r_t * e^{Lprev_t}) @ S_in                        [inter]
          + sum_{s<t} (sum_d r k e^{Lprev_t - Lcum_s}) v_s    [intra]
          + (r_t . u k_t) v_t                                 [bonus]
    S_out = diag(e^{Llast}) S_in + (k * e^{Llast - Lcum})^T v

Every exponent is non-positive, so the kernel is overflow-free by
construction (no max-subtraction pass needed).  The (C, C, D) decay tensor
is materialized in VMEM -- for C = D = 64 that is 1 MiB in f32, well within
a core's VMEM; this is the price of RWKV6's *per-channel* decay and the
reason the intra term is VPU- rather than MXU-bound (see DESIGN.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sout_ref, s, *, chunk):
    nc = pl.program_id(1)
    c = chunk
    d = r_ref.shape[-1]

    @pl.when(nc == 0)
    def _init():
        s[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)  # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (D,)

    lcum = jnp.cumsum(lw, axis=0)  # (C, D) inclusive
    lprev = jnp.concatenate([jnp.zeros((1, d), jnp.float32), lcum[:-1]], axis=0)

    # inter-chunk term (MXU): (C, D) @ (D, D)
    o_inter = jnp.dot(r * jnp.exp(lprev), s[...], preferred_element_type=jnp.float32)

    # intra-chunk term (VPU): per-channel decay prevents a pure matmul form
    diff = lprev[:, None, :] - lcum[None, :, :]  # (C, C, D), <= 0 for s < t
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    mask = (ti > si).astype(jnp.float32)
    g = jnp.einsum("td,sd,tsd->ts", r, k, jnp.exp(diff)) * mask
    bonus = jnp.sum(r * u[None, :] * k, axis=-1)  # (C,)
    o = o_inter + jnp.dot(g, v, preferred_element_type=jnp.float32) + bonus[:, None] * v
    o_ref[0] = o.astype(o_ref.dtype)

    # carry update (MXU): S_out = diag(e^Llast) S + (k*e^{Llast-Lcum})^T v
    llast = lcum[-1]  # (D,)
    kd = k * jnp.exp(llast[None, :] - lcum)
    s[...] = jnp.exp(llast)[:, None] * s[...] + jnp.dot(
        kd.T, v, preferred_element_type=jnp.float32
    )

    @pl.when(nc == pl.num_programs(1) - 1)
    def _flush():
        sout_ref[0] = s[...].astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(
    r: jax.Array,  # (BH, T, D)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,
    u: jax.Array,  # (BH, D)
    state: jax.Array,  # (BH, D, D)
    chunk: int = 64,
    *,
    interpret: bool,
):
    bh, t, d = r.shape
    assert t % chunk == 0, f"T={t} not divisible by chunk={chunk}"
    ncs = t // chunk
    seq = pl.BlockSpec((1, chunk, d), lambda i, j: (i, j, 0))
    per_bh_vec = pl.BlockSpec((1, d), lambda i, j: (i, 0))
    per_bh_mat = pl.BlockSpec((1, d, d), lambda i, j: (i, 0, 0))
    o, s_out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=(bh, ncs),
        in_specs=[seq, seq, seq, seq, per_bh_vec, per_bh_mat],
        out_specs=[seq, per_bh_mat],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), r.dtype),
            jax.ShapeDtypeStruct((bh, d, d), state.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(r, k, v, logw, u, state)
    return o, s_out
