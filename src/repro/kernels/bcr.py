"""Pallas TPU kernels: block cyclic reduction (SaP-E reduced-chain stage).

Log-depth counterpart of the sequential chain kernels in ``btf.py`` /
``bts.py``: one even/odd elimination level of the reduced interface chain
is a *parallel* grid over the m/2 even block rows -- no sequential VMEM
carry at all, the dependency depth lives in the O(log2 M) host-level loop
over ``pallas_call``s instead of in an O(M) grid walk.  Each grid cell
streams the handful of (K, K) blocks it touches from HBM via BlockSpec
index maps (neighbor access = clamped index map; the algebra zeroes the
clamped terms at the chain ends) and does pure MXU matmuls plus one
boosted Gauss-Jordan inversion.

Four kernels implement the two public entry points (the factor/solve
kernel pair dispatched by ``repro.kernels.ops``):

  bcr_factor_pallas : _inv_odd (invert odd diagonals)  +  _reduce
                      (build lo/hi and the half-length chain), per level
  bcr_solve_pallas  : _rhs_reduce (fold odd RHS into even equations)
                      going down, _backsub (recover odd unknowns,
                      interleave) coming back up

The pure-jnp oracle is ``repro.core.cyclic_reduction``; both paths build
the identical :class:`~repro.core.cyclic_reduction.BCRFactors` pytree.
Both inherit the structural-zero pivot exemption of
:func:`repro.core.block_lu.gj_inverse`: exactly-zero block rows (identity
padding) invert to identity slots instead of boosted ``1/thr`` garbage.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.block_lu import DEFAULT_BOOST, gj_inverse, mm
from repro.core.cyclic_reduction import BCRFactors, BCRLevel, pad_chain


def _inv_odd_kernel(d_ref, a_ref, *, boost_eps):
    d = d_ref[0].astype(jnp.float32)
    a_ref[0] = gj_inverse(d, boost_eps).astype(a_ref.dtype)


def _reduce_kernel(
    d_ref, e_ref, en_ref, ep_ref, f_ref, fn_ref, fp_ref, ac_ref, ap_ref,
    dn_ref, eo_ref, fo_ref, lo_ref, hi_ref,
):
    """One even row 2i of one elimination level.

    Inputs: D/E/F at 2i, E/F at 2i+1 (next) and 2i-1 (prev, clamped --
    E_{2i} = 0 at i = 0 kills the clamped terms exactly), inv(D) at odd
    2i+1 (ac) and 2i-1 (ap, clamped).  Outputs: the level-(l+1) chain
    blocks D'/E'/F' and the RHS-reduction multipliers lo/hi.
    """
    d = d_ref[0].astype(jnp.float32)
    e = e_ref[0].astype(jnp.float32)
    e_next = en_ref[0].astype(jnp.float32)
    e_prev = ep_ref[0].astype(jnp.float32)
    f = f_ref[0].astype(jnp.float32)
    f_next = fn_ref[0].astype(jnp.float32)
    f_prev = fp_ref[0].astype(jnp.float32)
    a_cur = ac_ref[0].astype(jnp.float32)
    a_prev = ap_ref[0].astype(jnp.float32)

    lo = mm(e, a_prev)
    hi = mm(f, a_cur)
    dn_ref[0] = (d - mm(lo, f_prev) - mm(hi, e_next)).astype(dn_ref.dtype)
    eo_ref[0] = (-mm(lo, e_prev)).astype(eo_ref.dtype)
    fo_ref[0] = (-mm(hi, f_next)).astype(fo_ref.dtype)
    lo_ref[0] = lo.astype(lo_ref.dtype)
    hi_ref[0] = hi.astype(hi_ref.dtype)


def _rhs_reduce_kernel(lo_ref, hi_ref, b_ref, bp_ref, bn_ref, out_ref):
    lo = lo_ref[0].astype(jnp.float32)
    hi = hi_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    b_prev = bp_ref[0].astype(jnp.float32)  # b_{2i-1}, clamped (lo_0 = 0)
    b_next = bn_ref[0].astype(jnp.float32)  # b_{2i+1}
    out_ref[0] = (b - mm(lo, b_prev) - mm(hi, b_next)).astype(out_ref.dtype)


def _backsub_kernel(a_ref, e_ref, f_ref, b_ref, x_ref, xn_ref, out_ref):
    """Recover odd unknown 2i+1 and interleave: out block = [x_{2i}; x_{2i+1}]."""
    a = a_ref[0].astype(jnp.float32)
    e = e_ref[0].astype(jnp.float32)
    f = f_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    x_even = x_ref[0].astype(jnp.float32)
    x_next = xn_ref[0].astype(jnp.float32)  # x_{2i+2}, clamped (f_odd end = 0)
    x_odd = mm(a, b - mm(e, x_even) - mm(f, x_next))
    out_ref[0] = x_even.astype(out_ref.dtype)
    out_ref[1] = x_odd.astype(out_ref.dtype)


def _specs(k, last, *idx_maps):
    return [
        pl.BlockSpec((1, k, last), imap) for imap in idx_maps
    ]


_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel",))


# ---------------------------------------------------------------------------
# Lane padding (ROADMAP item: small-K blocks vs the 8x128 fp32 tile)
# ---------------------------------------------------------------------------
#
# TPU vector memory tiles fp32 as (8, 128): the second-to-last dim must be
# a multiple of 8 and the last a multiple of 128 for the compiled Pallas
# path.  The reduced-chain block size K (and the RHS width R) are usually
# far below 128, so the compiled kernels embed each (K, K) block into a
# lane-aligned (K', K') block: D picks up an identity tail (decoupled
# rows that carry the zero solution), E / F / RHS pick up zeros.  The
# algebra is exact -- inv(blkdiag(A, I)) = blkdiag(inv(A), I) and all
# cross terms against the padded rows are zero -- so the padded factors
# solve the original chain bit-for-bit up to float roundoff; the solve
# slices the padding back off.  ``lane_pad=None`` enables padding exactly
# when the kernels compile for real (interpret=False); interpret-mode
# tests can force it on to validate the padded algebra on CPU.


def _lane_round(x: int) -> int:
    """Round a block dim up to the fp32 tile: mult of 8, last-dim 128."""
    return max(-(-x // 8) * 8, -(-x // 128) * 128)


def _resolve_lane_pad(lane_pad: bool | None, interpret: bool) -> bool:
    return (not interpret) if lane_pad is None else lane_pad


def _pad_block_dim(x: jax.Array, kp: int, identity: bool) -> jax.Array:
    """(m, K, K) -> (m, K', K'): identity (D blocks) or zero (E/F) tail."""
    m, k, _ = x.shape
    if kp == k:
        return x
    out = jnp.zeros((m, kp, kp), x.dtype)
    if identity:
        idx = jnp.arange(k, kp)
        out = out.at[:, idx, idx].set(1.0)
    return out.at[:, :k, :k].set(x)


def _pad_last(x: jax.Array, rp: int) -> jax.Array:
    """(m, K, R) -> (m, K, R'): zero-pad the trailing (lane) dim."""
    if rp == x.shape[-1]:
        return x
    pad = jnp.zeros(x.shape[:-1] + (rp - x.shape[-1],), x.dtype)
    return jnp.concatenate([x, pad], axis=-1)


def _reduce_level_pallas(d, e, f, boost_eps, interpret):
    """One elimination level: (m, K, K) chain -> level factors + m/2 chain."""
    m, k, _ = d.shape
    m2 = m // 2
    sd = jax.ShapeDtypeStruct

    a_odd = pl.pallas_call(
        functools.partial(_inv_odd_kernel, boost_eps=boost_eps),
        grid=(m2,),
        in_specs=_specs(k, k, lambda i: (2 * i + 1, 0, 0)),
        out_specs=pl.BlockSpec((1, k, k), lambda i: (i, 0, 0)),
        out_shape=sd((m2, k, k), d.dtype),
        interpret=interpret,
        compiler_params=_PARALLEL,
    )(d)

    cur = lambda i: (2 * i, 0, 0)
    nxt = lambda i: (2 * i + 1, 0, 0)
    prv = lambda i: (jnp.maximum(2 * i - 1, 0), 0, 0)
    a_cur = lambda i: (i, 0, 0)
    a_prv = lambda i: (jnp.maximum(i - 1, 0), 0, 0)
    d_n, e_n, f_n, lo, hi = pl.pallas_call(
        _reduce_kernel,
        grid=(m2,),
        in_specs=_specs(
            k, k, cur, cur, nxt, prv, cur, nxt, prv, a_cur, a_prv
        ),
        out_specs=_specs(k, k, *([a_cur] * 5)),
        out_shape=[sd((m2, k, k), d.dtype)] * 5,
        interpret=interpret,
        compiler_params=_PARALLEL,
    )(d, e, e, e, f, f, f, a_odd, a_odd)
    level = BCRLevel(lo=lo, hi=hi, a_odd=a_odd, e_odd=e[1::2], f_odd=f[1::2])
    return level, (d_n, e_n, f_n)


@functools.partial(
    jax.jit, static_argnames=("boost_eps", "interpret", "lane_pad")
)
def bcr_factor_pallas(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    boost_eps: float = DEFAULT_BOOST,
    *,
    interpret: bool,
    lane_pad: bool | None = None,
) -> BCRFactors:
    """Factor one chain (M, K, K) in log2(M) kernel-level rounds.

    ``lane_pad`` embeds small-K blocks into the (8, 128) fp32 tile before
    the kernels run (see the lane-padding note above); the returned
    factors then hold K'-sized blocks, which :func:`bcr_solve_pallas`
    detects and undoes.  Default ``None`` = pad iff compiling for real.
    """
    m, k = d.shape[0], d.shape[1]
    if _resolve_lane_pad(lane_pad, interpret):
        kp = _lane_round(k)
        d = _pad_block_dim(d, kp, identity=True)
        e = _pad_block_dim(e, kp, identity=False)
        f = _pad_block_dim(f, kp, identity=False)
    d, e, f = pad_chain(d, e, f)
    levels = []
    while d.shape[0] > 1:
        level, (d, e, f) = _reduce_level_pallas(d, e, f, boost_eps, interpret)
        levels.append(level)
    root_inv = gj_inverse(d[0].astype(jnp.float32), boost_eps).astype(d.dtype)
    return BCRFactors(levels=tuple(levels), root_inv=root_inv, m=m)


@functools.partial(jax.jit, static_argnames=("interpret", "lane_pad"))
def bcr_solve_pallas(
    factors: BCRFactors, b: jax.Array, *, interpret: bool,
    lane_pad: bool | None = None,
) -> jax.Array:
    """Solve one factored chain: b (M, K, R) -> x (M, K, R).

    Factors produced with lane padding carry K'-sized blocks; the RHS is
    embedded to match (zero rows) and the solution sliced back.  The RHS
    width R is itself a lane dim and gets zero-padded to the 128 tile
    whenever lane padding is active.
    """
    m, k0, r0 = b.shape
    kp = factors.root_inv.shape[-1]  # block dim the factors were built at
    if kp != k0:
        b = jnp.concatenate(
            [b, jnp.zeros((m, kp - k0, r0), b.dtype)], axis=1
        )
    if _resolve_lane_pad(lane_pad, interpret) or kp != k0:
        b = _pad_last(b, -(-r0 // 128) * 128)
    m, k, r = b.shape
    sd = jax.ShapeDtypeStruct
    m_pad = 2 ** len(factors.levels) if factors.levels else 1
    if m_pad != m:
        b = jnp.concatenate([b, jnp.zeros((m_pad - m, k, r), b.dtype)], 0)

    cur = lambda i: (i, 0, 0)
    saved_odd = []
    for lv in factors.levels:
        m2 = b.shape[0] // 2
        saved_odd.append(b[1::2])
        b = pl.pallas_call(
            _rhs_reduce_kernel,
            grid=(m2,),
            in_specs=_specs(k, k, cur, cur)
            + _specs(
                k,
                r,
                lambda i: (2 * i, 0, 0),
                lambda i: (jnp.maximum(2 * i - 1, 0), 0, 0),
                lambda i: (2 * i + 1, 0, 0),
            ),
            out_specs=pl.BlockSpec((1, k, r), cur),
            out_shape=sd((m2, k, r), b.dtype),
            interpret=interpret,
            compiler_params=_PARALLEL,
        )(lv.lo, lv.hi, b, b, b)

    x = mm(factors.root_inv, b[0])[None]
    for lv, b_odd in zip(reversed(factors.levels), reversed(saved_odd)):
        m2 = x.shape[0]
        x = pl.pallas_call(
            _backsub_kernel,
            grid=(m2,),
            in_specs=_specs(k, k, cur, cur, cur)
            + _specs(k, r, cur, cur, lambda i: (jnp.minimum(i + 1, m2 - 1), 0, 0)),
            out_specs=pl.BlockSpec((2, k, r), cur),
            out_shape=sd((2 * m2, k, r), x.dtype),
            interpret=interpret,
            compiler_params=_PARALLEL,
        )(lv.a_odd, lv.e_odd, lv.f_odd, b_odd, x, x)
    return x[:factors.m, :k0, :r0]
