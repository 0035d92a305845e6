"""Pallas TPU kernel: causal/windowed GQA flash attention.

Beyond-paper optimization motivated by the roofline analysis
(EXPERIMENTS.md section Perf): the jnp chunked-attention fallback
materializes (Bq, Bk) score blocks in HBM between kernels, which makes
every attention-heavy cell memory-bound.  This kernel keeps the running
(m, l, acc) online-softmax state and the score block in VMEM; its HBM
traffic is exactly q, k, v in + o out.

Grid: ``(B*Hkv, Tq/Bq, Tk/Bk)`` with the kv axis innermost (sequential).
Causal + sliding-window masks are applied per block; blocks that are
entirely masked skip their matmuls via ``pl.when`` (the causal 2x FLOP
waste of the fallback disappears).  GQA: the G query heads of one KV head
are folded into the q-block rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q, block_k, tk, causal, window, scale):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_k
    # block-level skip: entirely-future (causal) or entirely-outside-window
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(
            run, k_start + block_k - 1 >= q_start - (window - 1)
        )

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)  # (G*Bq, D)
        k = k_ref[0].astype(jnp.float32)  # (Bk, D)
        v = v_ref[0].astype(jnp.float32)  # (Bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (G*Bq, Bk)

        g_bq = q.shape[0]
        g = g_bq // block_q
        # row r of s corresponds to query position q_start + (r % block_q)
        ridx = jax.lax.broadcasted_iota(jnp.int32, (g_bq, block_k), 0)
        q_pos = q_start + jnp.remainder(ridx, block_q)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (g_bq, block_k), 1
        )
        mask = k_pos < tk
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(kj == nk - 1)
    def _flush():
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, Hq, Tq, D)
    k: jax.Array,  # (B, Hk, Tk, D)
    v: jax.Array,  # (B, Hk, Tk, D)
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    *,
    interpret: bool,
):
    b, hq, tq, d = q.shape
    hk, tk = k.shape[1], k.shape[2]
    g = hq // hk
    assert tq % block_q == 0 and tk % block_k == 0
    scale = 1.0 / (d ** 0.5)

    # fold (B, Hk) into the grid; interleave the G query heads of one KV
    # head into each q-block (one block = G copies of its Bq rows)
    qg = (
        q.reshape(b, hk, g, tq, d)
        .reshape(b * hk, g, tq, d)
        .transpose(0, 2, 1, 3)  # (BHk, Tq, G, D)
        .reshape(b * hk, tq // block_q, block_q, g, d)
        .transpose(0, 1, 3, 2, 4)  # (BHk, nq, G, Bq, D)
        .reshape(b * hk, tq // block_q * g * block_q, d)
    )
    kf = k.reshape(b * hk, tk, d)
    vf = v.reshape(b * hk, tk, d)

    nq = tq // block_q
    nk = tk // block_k
    q_spec = pl.BlockSpec((1, g * block_q, d), lambda h, i, j: (h, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0))
    o = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_q=block_q, block_k=block_k, tk=tk,
            causal=causal, window=window, scale=scale,
        ),
        grid=(b * hk, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * hk, tq * g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * block_q, 1), jnp.float32),
            pltpu.VMEM((g * block_q, 1), jnp.float32),
            pltpu.VMEM((g * block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(qg, kf, vf)

    # undo the interleaved layout
    o = (
        o.reshape(b * hk, nq, g, block_q, d)
        .transpose(0, 2, 1, 3, 4)  # (BHk, G, nq, Bq, D)
        .reshape(b, hk, g, tq, d)
        .reshape(b, hq, tq, d)
    )
    return o
