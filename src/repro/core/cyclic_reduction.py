"""Block cyclic reduction (BCR) for block-tridiagonal chains.

The SaP-E exact coupling (``repro.core.spike``, paper Sec. 2.1.1) ends in
a (P-1)-interface block-tridiagonal *chain* of (2K x 2K) blocks.  The
``btf_chain``/``bts_chain`` factorization sweeps that chain sequentially:
O(M) dependent steps, the one part of the preconditioner that does not
parallelize.  Cyclic reduction replaces the sweep with even/odd
elimination:

  level 0:   eliminate the odd-indexed unknowns from the even equations
             (every elimination is independent -> fully parallel),
             leaving a block-tridiagonal chain of half the length;
  level l:   recurse on the survivors;
  level L-1: a single block remains -- invert it;
  back-substitution mirrors the levels in reverse, recovering the odd
             unknowns from their (already solved) even neighbors.

O(log2 M) parallel steps in place of O(M) sequential ones -- the same
interface-system strategy that makes sub-structuring methods scale across
GPUs (Cheik Ahamed & Magoules, arXiv:2108.13162) and that parallel
triangular-solve work identifies as the key to beating level-by-level
sweeps (Li, arXiv:1710.04985).

Eliminating odd unknown x_j (j odd) via its own equation

    x_j = inv(D_j) (b_j - E_j x_{j-1} - F_j x_{j+1})

and substituting into the even equations j = 2i gives the level-(l+1)
chain over the even unknowns:

    lo_i  = E_{2i} inv(D_{2i-1})          hi_i = F_{2i} inv(D_{2i+1})
    D'_i  = D_{2i} - lo_i F_{2i-1} - hi_i E_{2i+1}
    E'_i  = -lo_i E_{2i-1}                F'_i = -hi_i F_{2i+1}
    b'_i  = b_{2i} - lo_i b_{2i-1} - hi_i b_{2i+1}

Chains are padded to a power of two with decoupled identity blocks
(D = I, E = F = 0, b = 0), so non-power-of-two lengths work unchanged.

Two factored forms live here:

* :func:`bcr_factor` / :func:`bcr_solve` -- the classic (work-optimal)
  even/odd recursion above, for a chain resident on one device.  The
  Pallas kernel pair in ``repro.kernels.bcr`` implements the same level
  updates; dispatch through ``repro.kernels.ops`` (ref/interpret/pallas).

* :func:`pcr_factor` / :func:`pcr_solve` -- the all-active *parallel*
  cyclic reduction (PCR) form, in which every equation eliminates both
  neighbors at distance s = 2^l each level and no unknown ever goes
  idle.  PCR does O(M log M) work but each level touches only neighbors
  at a fixed stride, which maps 1:1 onto ``ppermute`` shift rounds over a
  device mesh -- ``repro.core.distributed`` uses it for the sharded
  SaP-E reduced sweep (the chain never gathers onto one device).  The
  shift primitive is injected so the identical code runs single-device
  (array shifts, used by the tests as the oracle) and under ``shard_map``
  (collective shifts).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .block_lu import DEFAULT_BOOST, gj_inverse, mm


def _next_pow2(m: int) -> int:
    return 1 if m <= 1 else 1 << (m - 1).bit_length()


def _shift_dn(x: jax.Array, s: int = 1) -> jax.Array:
    """x[i] <- x[i-s] along axis 0; the first s rows get zeros."""
    return jnp.concatenate([jnp.zeros_like(x[:s]), x[:-s]], axis=0)


def _shift_up(x: jax.Array, s: int = 1) -> jax.Array:
    """x[i] <- x[i+s] along axis 0; the last s rows get zeros."""
    return jnp.concatenate([x[s:], jnp.zeros_like(x[:s])], axis=0)


def _vinv(a: jax.Array, boost_eps: float) -> jax.Array:
    return jax.vmap(lambda blk: gj_inverse(blk, boost_eps))(a)


def pad_chain(
    d: jax.Array, e: jax.Array, f: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Zero the unused end blocks and pad with identity blocks to 2^L.

    The padding blocks are decoupled (D = I, E = F = 0): they carry the
    zero solution and never touch the real chain.
    """
    m, k, _ = d.shape
    e = e.at[0].set(0.0)
    f = f.at[m - 1].set(0.0)
    m_pad = _next_pow2(m)
    if m_pad == m:
        return d, e, f
    extra = m_pad - m
    eye = jnp.broadcast_to(jnp.eye(k, dtype=d.dtype), (extra, k, k))
    zero = jnp.zeros((extra, k, k), d.dtype)
    return (
        jnp.concatenate([d, eye], axis=0),
        jnp.concatenate([e, zero], axis=0),
        jnp.concatenate([f, zero], axis=0),
    )


# ---------------------------------------------------------------------------
# Classic even/odd recursion (single chain, log2(M) levels)
# ---------------------------------------------------------------------------


class BCRLevel(NamedTuple):
    """One elimination level; all arrays are (m_l / 2, K, K).

    lo/hi multiply the odd RHS neighbors in the forward reduction;
    a_odd (= inv(D_odd)), e_odd, f_odd drive the back-substitution.
    """

    lo: jax.Array
    hi: jax.Array
    a_odd: jax.Array
    e_odd: jax.Array
    f_odd: jax.Array


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("levels", "root_inv"),
    meta_fields=("m",),
)
@dataclasses.dataclass
class BCRFactors:
    """Log-depth factorization of one block-tridiagonal chain.

    levels[l] holds the level-l elimination blocks (chain length 2^(L-l));
    root_inv is the inverse of the final surviving (K, K) block; ``m`` is
    the true (un-padded) chain length.
    """

    levels: Tuple[BCRLevel, ...]
    root_inv: jax.Array
    m: int

    @property
    def n_levels(self) -> int:
        return len(self.levels)


def bcr_reduce_level_ref(
    d: jax.Array, e: jax.Array, f: jax.Array, boost_eps: float = DEFAULT_BOOST
):
    """One even/odd elimination level (pure jnp; the kernels' oracle).

    Input chain (m, K, K) with m even -> (BCRLevel, d', e', f') of length
    m/2.  All products are batched (K, K) matmuls: MXU-shaped, and every
    one of the m/2 eliminations is independent.
    """
    a_odd = _vinv(d[1::2], boost_eps)
    e_odd, f_odd = e[1::2], f[1::2]
    # E_0 = 0 kills the (clamped) i = 0 down-neighbor terms.
    lo = mm(e[0::2], _shift_dn(a_odd))  # E_{2i} inv(D_{2i-1})
    hi = mm(f[0::2], a_odd)  # F_{2i} inv(D_{2i+1})
    d_next = d[0::2] - mm(lo, _shift_dn(f_odd)) - mm(hi, e_odd)
    e_next = -mm(lo, _shift_dn(e_odd))
    f_next = -mm(hi, f_odd)
    return BCRLevel(lo=lo, hi=hi, a_odd=a_odd, e_odd=e_odd, f_odd=f_odd), (
        d_next,
        e_next,
        f_next,
    )


def bcr_factor(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    boost_eps: float = DEFAULT_BOOST,
) -> BCRFactors:
    """Factor a block-tridiagonal chain (M, K, K) in log2(M) levels.

    Drop-in alternative to :func:`repro.core.block_lu.btf_chain` (pair
    with :func:`bcr_solve`); ``e[0]`` / ``f[M-1]`` are ignored.  Pivot
    stability comes from the same boosted Gauss-Jordan inversion; like
    the truncated-SPIKE stages, cyclic reduction is elimination without
    pivoting across blocks, which the paper's SaP setting accepts by
    construction (boosting, Sec. 2.2).
    """
    m = d.shape[0]
    d, e, f = pad_chain(d, e, f)
    levels = []
    while d.shape[0] > 1:
        level, (d, e, f) = bcr_reduce_level_ref(d, e, f, boost_eps)
        levels.append(level)
    root_inv = gj_inverse(d[0], boost_eps)
    return BCRFactors(levels=tuple(levels), root_inv=root_inv, m=m)


def bcr_solve(factors: BCRFactors, b: jax.Array) -> jax.Array:
    """Solve one factored chain: b (M, K, R) -> x (M, K, R).

    Forward: log2(M) RHS reductions; root: one (K, K) apply; backward:
    log2(M) interleaving back-substitutions.  Matches
    :func:`repro.core.block_lu.bts_chain` to factorization-dtype accuracy.
    """
    m, k, r = b.shape
    m_pad = _next_pow2(m)
    if m_pad != m:
        b = jnp.concatenate(
            [b, jnp.zeros((m_pad - m, k, r), b.dtype)], axis=0
        )
    saved_odd = []
    for lv in factors.levels:
        b_odd = b[1::2]
        saved_odd.append(b_odd)
        b = b[0::2] - mm(lv.lo, _shift_dn(b_odd)) - mm(lv.hi, b_odd)
    x = mm(factors.root_inv, b[0])[None]
    for lv, b_odd in zip(reversed(factors.levels), reversed(saved_odd)):
        # F_odd of the chain tail is zero, killing the clamped up-neighbor.
        x_odd = mm(lv.a_odd, b_odd - mm(lv.e_odd, x) - mm(lv.f_odd, _shift_up(x)))
        x = jnp.stack([x, x_odd], axis=1).reshape(2 * x.shape[0], k, r)
    return x[:m]


# ---------------------------------------------------------------------------
# All-active parallel cyclic reduction (the distributed sweep)
# ---------------------------------------------------------------------------


def pcr_n_levels(m: int) -> int:
    """Levels needed to decouple a chain of length m: smallest L with
    2^L >= m (after which every coupling block has been driven to zero)."""
    return max(m - 1, 0).bit_length()


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("alphas", "betas", "dinv"),
    meta_fields=(),
)
@dataclasses.dataclass
class PCRFactors:
    """All-active PCR factorization of a (distributed) chain.

    alphas/betas: (rows, L, K, K) per-level neighbor-elimination blocks
    (row-major so the leading axis shards like every other partition
    array); dinv: (rows, K, K) inverses of the fully decoupled diagonal.
    """

    alphas: jax.Array
    betas: jax.Array
    dinv: jax.Array

    @property
    def n_levels(self) -> int:
        return self.alphas.shape[1]


def pcr_factor(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    n_levels: int,
    shift_dn=None,
    shift_up=None,
    boost_eps: float = DEFAULT_BOOST,
) -> PCRFactors:
    """PCR matrix reduction: every equation eliminates both neighbors at
    stride s = 2^l per level; after ``n_levels`` levels the chain is block
    diagonal.

    ``shift_dn(x, s)`` / ``shift_up(x, s)`` fetch the row s positions
    away (zero fill past the ends).  The defaults operate on a local
    array; ``repro.core.distributed`` injects ``ppermute``-based shifts,
    making each level one neighbor-exchange round over the mesh --
    O(log2 P) rounds total, and the chain never gathers onto one device.

    Rows past the chain end must be decoupled identity padding (see
    :func:`pad_chain`).  Each level inverts the diagonal once and shifts
    the *inverse* both ways; couplings to out-of-range rows are exactly
    zero by induction, so the zero-filled shifted inverse is benign.
    """
    if shift_dn is None:
        shift_dn = _shift_dn
    if shift_up is None:
        shift_up = _shift_up
    rows, k, _ = d.shape
    alphas, betas = [], []
    for lev in range(n_levels):
        s = 1 << lev
        dinv = _vinv(d, boost_eps)
        alpha = mm(e, shift_dn(dinv, s))
        beta = mm(f, shift_up(dinv, s))
        d = d - mm(alpha, shift_dn(f, s)) - mm(beta, shift_up(e, s))
        e_new = -mm(alpha, shift_dn(e, s))
        f_new = -mm(beta, shift_up(f, s))
        e, f = e_new, f_new
        alphas.append(alpha)
        betas.append(beta)
    stack = lambda xs: (
        jnp.stack(xs, axis=1)
        if xs
        else jnp.zeros((rows, 0, k, k), d.dtype)
    )
    return PCRFactors(
        alphas=stack(alphas), betas=stack(betas), dinv=_vinv(d, boost_eps)
    )


def pcr_solve(
    factors: PCRFactors, b: jax.Array, shift_dn=None, shift_up=None
) -> jax.Array:
    """Apply a PCR factorization to a RHS block b (rows, K, R).

    One shift pair + two batched matmuls per level, then the decoupled
    diagonal apply -- the log-depth replacement for the forward/backward
    chain sweeps.
    """
    if shift_dn is None:
        shift_dn = _shift_dn
    if shift_up is None:
        shift_up = _shift_up
    for lev in range(factors.n_levels):
        s = 1 << lev
        b = (
            b
            - mm(factors.alphas[:, lev], shift_dn(b, s))
            - mm(factors.betas[:, lev], shift_up(b, s))
        )
    return mm(factors.dinv, b)


def resolve_reduced_solver(reduced_solver: str, m: int) -> str:
    """The ``"auto"`` policy for the SaP-E reduced chain solver.

    Cyclic reduction wins once the chain is long enough for its log-depth
    to beat the sequential sweep's lower constant; short chains (few
    partitions) stay on the ``btf_chain`` sweep.
    """
    if reduced_solver not in ("chain", "bcr", "auto"):
        raise ValueError(f"unknown reduced_solver {reduced_solver!r}")
    if reduced_solver != "auto":
        return reduced_solver
    return "bcr" if m >= 8 else "chain"
