"""Block-tridiagonal LU / UL factorization -- pure-jnp reference.

This is the TPU adaptation of the paper's dense-banded LU (Sec. 3.1): the
scalar "window sliding" factorization (a GPU warp/thread-block mechanism)
is re-cast as a *block*-tridiagonal factorization with (K x K) blocks, so
every update step is a (K x K) matmul that maps onto the MXU.  For a banded
matrix with half-bandwidth K this block factorization is exact.

    A_i = L_i @ U_i,     L_i unit block-lower-bidiagonal (blocks L_j),
                         U_i block-upper-bidiagonal (diag S_j, super F_j)

    S_0 = D_0
    L_j = E_j @ inv(S_{j-1})          j = 1..M-1
    S_j = D_j - L_j @ F_{j-1}

Pivoting is replaced by *pivot boosting* (paper Sec. 2.2, following
PARDISO): inside the Gauss-Jordan inversion of each S_j, any pivot smaller
than ``boost_eps * max|S_j|`` is boosted to that threshold.

*Structurally* zero rows are exempt from boosting: a row of S_j that is
exactly zero cannot come from rounding -- it is a decoupled slot (identity
padding from shape bucketing, or a band stored wider than its true
bandwidth).  Boosting such a pivot to ``thr`` injects a ``1/thr`` row into
the inverse and poisons every Schur complement downstream; instead the
pivot is treated as exactly 1, so the inverse restricted to those slots is
the identity -- the blkdiag(A, I) semantics the padded embeddings rely on.

The Pallas kernels in ``repro.kernels`` implement exactly these recurrences;
this module doubles as their oracle (re-exported by ``kernels/ref.py``).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

DEFAULT_BOOST = 1e-10


def mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at full precision: every matmul of the solver uses it.

    TPU's DEFAULT matmul precision rounds f32 operands to bf16.  The SaP
    apply must be one fixed linear operator to f32 accuracy, or the Krylov
    recurrence residual drifts away from the true one: on a v5e the solver
    reported convergence below 1e-6 with a true residual of 2.3e-3.
    HIGHEST keeps every bit of the operands (bf16 operands stay bf16).
    On CPU the setting changes nothing.
    """
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Gauss-Jordan inverse with pivot boosting (K x K)
# ---------------------------------------------------------------------------


# Largest block the pivot loop inverts whole: 128 lanes, so the block and
# its inverse are 2 x 16 vregs at most and stay in registers.
GJ_LEAF = 128


def gj_inverse(a: jax.Array, boost_eps: float = DEFAULT_BOOST) -> jax.Array:
    """Inverse of a (K, K) block via Gauss-Jordan with pivot boosting.

    A pivot smaller than ``thr = boost_eps * max|a|`` is boosted to
    ``+-thr``, so the result is the exact inverse of ``a + dA`` with a
    diagonal ``dA`` (paper Sec. 2.2).  Rows of ``a`` that are *exactly*
    zero (structurally decoupled slots, e.g. identity padding) are never
    boosted: their pivot is taken as 1, so the returned inverse acts as the
    identity on those slots instead of a ``1/thr``-sized perturbation.

    Up to ``GJ_LEAF`` the pivot loop of :func:`_gj_leaf` inverts the block
    whole.  Above it the block is inverted by the 2 x 2 block (Schur
    complement) recursion of :func:`_gj_blocked`: the same pivots in the
    same order and the same ``thr``, with the O(K^3) work in matmuls (the
    MXU in a kernel) and the pivot loops only on lane-wide diagonal
    leaves.  Only the order of the roundings differs from one loop over
    all K pivots.
    """
    if a.shape[-1] <= GJ_LEAF:
        return _gj_leaf(a, _boost_threshold(a, boost_eps))
    return _gj_inverse_blocked(a, boost_eps)


def _boost_threshold(a: jax.Array, boost_eps: float) -> jax.Array:
    """``boost_eps * max|a|`` as a (1, 1) array: the whole block's pivot
    boosting threshold."""
    scale = jnp.max(jnp.max(jnp.abs(a), axis=1, keepdims=True), axis=0,
                    keepdims=True)
    return boost_eps * jnp.maximum(scale, jnp.asarray(1e-30, a.dtype))


@partial(jax.jit, static_argnames=("boost_eps",))
def _gj_inverse_blocked(a: jax.Array, boost_eps: float) -> jax.Array:
    """The blocked inverse as one compiled unit: a caller outside ``jit``
    (the reduced systems' ``vmap``) then compiles it once per shape rather
    than dispatching its slices, products and two pivot loops one by one."""
    return _gj_blocked(a, _boost_threshold(a, boost_eps), None)


def _gj_leaf(
    a: jax.Array, thr: jax.Array, outside: jax.Array | None = None
) -> jax.Array:
    """The boosted Gauss-Jordan pivot loop over all K pivots of ``a``.

    ``outside`` (K, 1), when given, is max |entry| of each row in the
    columns of the enclosing block that lie right of ``a``: a row with a
    nonzero there is not structurally zero, so it is boosted like any
    other, never exempt.  Elimination never fills a zero row (its
    multiplier column entry is zero), so the test at step ``t`` sees the
    original structure of row ``t``.

    The loop body does no dynamic indexing: row and column ``t`` are
    selected with ``iota == t`` masks (a masked sum adds only zeros to the
    one selected entry, so it is exact), and the left block and the
    inverse are carried as two (K, K) arrays rather than one (K, 2K)
    concatenation.  Mosaic (the Pallas TPU compiler) lowers neither
    dynamic slices nor dynamic updates inside a kernel loop, and the
    kernels and the jnp oracles share this one function.
    """
    k = a.shape[-1]
    dtype = a.dtype
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    inv = (rows == cols).astype(dtype)
    zero = jnp.zeros((), dtype)

    def step(t, carry):
        a, inv = carry
        rsel = rows == t
        csel = cols == t
        a_row = jnp.sum(jnp.where(rsel, a, zero), axis=0, keepdims=True)
        i_row = jnp.sum(jnp.where(rsel, inv, zero), axis=0, keepdims=True)
        col = jnp.sum(jnp.where(csel, a, zero), axis=1, keepdims=True)
        at_t = csel[:1]  # (1, K): column t of a row vector
        piv = jnp.sum(jnp.where(at_t, a_row, zero), axis=1, keepdims=True)
        live = jnp.max(jnp.abs(a_row), axis=1, keepdims=True)
        if outside is not None:  # row t of the (K, 1) column ``outside``
            live = jnp.maximum(live, jnp.sum(
                jnp.where(rows[:, :1] == t, outside, zero), axis=0,
                keepdims=True))
        struct_zero = live == 0
        piv = jnp.where(
            jnp.abs(piv) < thr, jnp.where(piv >= 0, thr, -thr), piv
        )
        piv = jnp.where(struct_zero, jnp.ones((), dtype), piv)
        # normalize pivot row; treat a[t, t] as the (possibly boosted) piv,
        # i.e. we factor the perturbed block A + dA (paper Sec. 2.2)
        a_row = jnp.where(at_t, jnp.ones((), dtype), a_row / piv)
        i_row = i_row / piv
        a = jnp.where(rsel, a_row, a - col * a_row)
        inv = jnp.where(rsel, i_row, inv - col * i_row)
        return a, inv

    _, inv = jax.lax.fori_loop(0, k, step, (a, inv))
    return inv


def _gj_blocked(
    a: jax.Array, thr: jax.Array, outside: jax.Array | None
) -> jax.Array:
    """Boosted inverse of ``a`` by the 2 x 2 block (Schur) recursion.

    With ``a = [[A11, A12], [A21, A22]]`` split at ``h``, a multiple of
    128 (``h = 128 * ceil(K / 256)``: 200 -> 128 + 72, 400 -> 256 + 144):

        inv11 = inv(A11)            pivots 0 .. h-1
        S     = A22 - A21 inv11 A12
        invS  = inv(S)              pivots h .. K-1
        inv(a) = [[inv11 + X invS Y, -X invS], [-invS Y, invS]]

    with ``X = inv11 A12`` and ``Y = A21 inv11``.  These are the pivots of
    one Gauss-Jordan sweep over ``a``, so boosting them with the whole
    block's ``thr`` inverts the same ``a + dA``.  A row of A11 is
    structurally zero only if its A12 part is zero too, so A12's row
    maxima join ``outside`` for the A11 leaf; the rows of S own their
    whole current row.  Slices and concatenations are static and at
    multiples of 128, which Mosaic lowers.
    """
    k = a.shape[-1]
    if k <= GJ_LEAF:
        return _gj_leaf(a, thr, outside)
    h = GJ_LEAF * -(-k // (2 * GJ_LEAF))
    a11, a12 = a[:h, :h], a[:h, h:]
    a21, a22 = a[h:, :h], a[h:, h:]
    out1 = jnp.max(jnp.abs(a12), axis=1, keepdims=True)
    out2 = None
    if outside is not None:
        out1 = jnp.maximum(outside[:h], out1)
        out2 = outside[h:]
    inv11 = _gj_blocked(a11, thr, out1)
    x = mm(inv11, a12)
    y = mm(a21, inv11)
    inv_s = _gj_blocked(a22 - mm(a21, x), thr, out2)
    b12 = -mm(x, inv_s)
    b21 = -mm(inv_s, y)
    b11 = inv11 - mm(b12, y)
    return jnp.concatenate(
        [jnp.concatenate([b11, b12], axis=1),
         jnp.concatenate([b21, inv_s], axis=1)], axis=0)


def gj_solve(a: jax.Array, b: jax.Array, boost_eps: float = DEFAULT_BOOST) -> jax.Array:
    """Solve (K,K) @ x = (K,R) via the boosted inverse (small systems)."""
    return mm(gj_inverse(a, boost_eps), b)


# ---------------------------------------------------------------------------
# Factorization
# ---------------------------------------------------------------------------


class BTFactors(NamedTuple):
    """Factors of the block-diagonal matrix D = diag(A_1..A_P).

    sinv: (P, M, K, K)  inverses of the block pivots S_j
    l:    (P, M, K, K)  unit-lower block multipliers (l[:, 0] zero)
    f:    (P, M, K, K)  super-diagonal blocks (copied from input)
    """

    sinv: jax.Array
    l: jax.Array
    f: jax.Array


@partial(jax.jit, static_argnames=("boost_eps",))
def btf_ref(
    d: jax.Array, e: jax.Array, f: jax.Array, boost_eps: float = DEFAULT_BOOST
) -> BTFactors:
    """Block-tridiagonal factorization of every partition (vmap over P)."""

    def one_partition(dp, ep, fp):
        m, k, _ = dp.shape

        def step(carry, blocks):
            sinv_prev = carry
            dj, ej, fj_prev = blocks
            lj = mm(ej, sinv_prev)
            sj = dj - mm(lj, fj_prev)
            sinvj = gj_inverse(sj, boost_eps)
            return sinvj, (sinvj, lj)

        s0 = dp[0]
        sinv0 = gj_inverse(s0, boost_eps)
        # blocks j = 1..M-1 paired with F_{j-1}
        xs = (dp[1:], ep[1:], fp[:-1])
        _, (sinv_rest, l_rest) = jax.lax.scan(step, sinv0, xs)
        sinv = jnp.concatenate([sinv0[None], sinv_rest], axis=0)
        l = jnp.concatenate([jnp.zeros_like(sinv0)[None], l_rest], axis=0)
        return sinv, l

    sinv, l = jax.vmap(one_partition)(d, e, f)
    return BTFactors(sinv=sinv, l=l, f=f)


# ---------------------------------------------------------------------------
# Solve  D @ x = b  (independent per partition)
# ---------------------------------------------------------------------------


@jax.jit
def bts_ref(factors: BTFactors, b: jax.Array) -> jax.Array:
    """Solve with the factors.  b: (P, M, K, R) -> x: (P, M, K, R)."""

    sinv, l, f = factors

    def one_partition(sinvp, lp, fp, bp):
        # forward:  y_j = b_j - L_j y_{j-1}
        def fwd(y_prev, blocks):
            lj, bj = blocks
            yj = bj - mm(lj, y_prev)
            return yj, yj

        y0 = bp[0]
        _, y_rest = jax.lax.scan(fwd, y0, (lp[1:], bp[1:]))
        y = jnp.concatenate([y0[None], y_rest], axis=0)

        # backward: x_{M-1} = Sinv y_{M-1};  x_j = Sinv_j (y_j - F_j x_{j+1})
        def bwd(x_next, blocks):
            sinvj, fj, yj = blocks
            xj = mm(sinvj, yj - mm(fj, x_next))
            return xj, xj

        x_last = mm(sinvp[-1], y[-1])
        _, x_rest = jax.lax.scan(
            bwd, x_last, (sinvp[:-1], fp[:-1], y[:-1]), reverse=True
        )
        return jnp.concatenate([x_rest, x_last[None]], axis=0)

    return jax.vmap(one_partition)(sinv, l, f, b)


# ---------------------------------------------------------------------------
# Single-chain convenience (the SaP-E reduced interface system, Sec. 2.1)
# ---------------------------------------------------------------------------


def btf_chain(
    d: jax.Array, e: jax.Array, f: jax.Array, boost_eps: float = DEFAULT_BOOST
) -> BTFactors:
    """Factor a single block-tridiagonal chain (M, K, K).

    Adds the partition axis around :func:`btf_ref` so the same recurrences
    factor *one* chain; used recursively by the SaP-E exact reduced
    interface system (``repro.core.spike``), whose (P-1) coupled interface
    blocks of size 2K form exactly such a chain.  The returned factors keep
    the leading singleton partition axis (pair with :func:`bts_chain`).
    """
    return btf_ref(d[None], e[None], f[None], boost_eps)


def bts_chain(factors: BTFactors, b: jax.Array) -> jax.Array:
    """Solve one factored chain: b (M, K, R) -> x (M, K, R)."""
    return bts_ref(factors, b[None])[0]


# ---------------------------------------------------------------------------
# UL factorization via reversal (for the left-spike top blocks, Sec. 2.1)
# ---------------------------------------------------------------------------


def flip_block_tridiag(
    d: jax.Array, e: jax.Array, f: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Blocks of J A J^T (row+col reversal) per partition.

    Reversal maps block (r, c) -> (M-1-r, M-1-c) and flips each block on
    both axes.  An LU factorization of the reversed matrix is a UL
    factorization of the original (paper Sec. 2.1: the alternative to
    computing the whole left spike W_i).
    """

    def flip2(x):
        return x[..., ::-1, ::-1]

    d_r = flip2(d[:, ::-1])
    # sub-diag of reversed row j is the flipped super-diag of row M-1-j
    e_r = flip2(f[:, ::-1])
    f_r = flip2(e[:, ::-1])
    # fix unused slots
    m = d.shape[1]
    e_r = e_r.at[:, 0].set(0.0)
    f_r = f_r.at[:, m - 1].set(0.0)
    return d_r, e_r, f_r


@partial(jax.jit, static_argnames=("boost_eps",))
def btf_ul_ref(
    d: jax.Array, e: jax.Array, f: jax.Array, boost_eps: float = DEFAULT_BOOST
) -> BTFactors:
    """UL factors == LU factors of the reversed partition."""
    d_r, e_r, f_r = flip_block_tridiag(d, e, f)
    return btf_ref(d_r, e_r, f_r, boost_eps)


# ---------------------------------------------------------------------------
# Fused factor + spike extraction (single ascending pass, Sec. 2.1 + 3.1)
# ---------------------------------------------------------------------------
#
# The SaP preconditioner needs, besides the LU factors of each partition,
# the four corner blocks of the spikes:
#
#   v_bot[i] = Sinv_i[M-1] @ B_i                     (right spike, bottom)
#   v_top[i] = top block of A_i^{-1} [0;..;B_i]      (right spike, top)
#   w_top[i] = top block of A_{i+1}^{-1} [C_{i+1};0;..]   (left spike, top)
#   w_bot[i] = bottom block of the same left spike
#
# The kernel-sequence formulation materializes a full UL factorization
# (w_top) and solves whole K-column spikes through bts (v_top / w_bot),
# each round-tripping (P, M, K, K) intermediates through HBM.  All four
# corners are available from ONE ascending sweep j = 0..M-1 that carries
# four K x K blocks:
#
#   * the LU recurrence (sinv_prev), emitting sinv_j / l_j as usual;
#   * the UL recurrence, i.e. the LU recurrence on the reversed chain
#     (flip_block_tridiag) -- only its carry is kept, no UL factors are
#     ever written;
#   * the left-spike RHS swept forward through LU:  y_0 = C_i,
#     y_j = -l_j y_{j-1}  (the rhs is zero past block 0), so
#     w_bot = sinv_{M-1} y_{M-1} needs no backward substitution;
#   * the right-spike RHS swept forward through UL:  yr_0 = flip(B_i),
#     yr_j = -l^{UL}_j yr_{j-1}, so v_top = flip(sinv^{UL}_{M-1} yr_{M-1}).
#
# ``fused_factor_spike_padded_ref`` is the op-for-op oracle of the Pallas
# megakernel in ``repro.kernels.fused_spike`` (bit-level parity in
# interpret mode); ``fused_factor_spike_ref`` wraps it with the
# (P-1)-interface coupling layout used by ``repro.core.spike``.


class FusedSpikeFactors(NamedTuple):
    """LU factors plus the four spike corner blocks, from one fused pass.

    lu:     factors of diag(A_1..A_P) (identical to :func:`btf_ref`)
    v_bot:  (P-1, K, K)  bottom blocks of the right spikes V_i,  i=0..P-2
    v_top:  (P-1, K, K)  top blocks of the same right spikes
    w_top:  (P-1, K, K)  top blocks of the left spikes W_{i+1}
    w_bot:  (P-1, K, K)  bottom blocks of the same left spikes
    """

    lu: BTFactors
    v_bot: jax.Array
    v_top: jax.Array
    w_top: jax.Array
    w_bot: jax.Array


def _flip2(x: jax.Array) -> jax.Array:
    return x[..., ::-1, ::-1]


def _fliprows(x: jax.Array) -> jax.Array:
    return x[..., ::-1, :]


@partial(jax.jit, static_argnames=("boost_eps",))
def fused_factor_spike_padded_ref(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    bq: jax.Array,
    cq: jax.Array,
    boost_eps: float = DEFAULT_BOOST,
):
    """Fused factor+spike pass on per-partition padded couplings.

    d/e/f: (P, M, K, K); bq/cq: (P, K, K) -- the coupling block *of each
    partition* (``bq[p] = B_p`` or zero for the last partition,
    ``cq[p] = C_p`` or zero for the first), so every partition is an
    independent chain and a batch axis can fold straight into P.

    Returns ``(sinv, l, vb, vt, wt, wb)`` with sinv/l of shape
    (P, M, K, K) and the corners (P, K, K); corner blocks of partitions
    whose coupling is zero come out exactly zero.
    """
    p, m, k, _ = d.shape

    def one_partition(dp, ep, fp, bqp, cqp):
        sinv0 = gj_inverse(dp[0], boost_eps)
        sinv_ul0 = gj_inverse(_flip2(dp[m - 1]), boost_eps)

        def step(carry, blocks):
            sinv_prev, sinv_ul_prev, yw, yv = carry
            dj, ej, fjm1, drj, erj, frm1 = blocks
            lj = mm(ej, sinv_prev)
            sj = dj - mm(lj, fjm1)
            sinvj = gj_inverse(sj, boost_eps)
            yw = -mm(lj, yw)
            l_ul = mm(erj, sinv_ul_prev)
            s_ul = drj - mm(l_ul, frm1)
            sinv_ul = gj_inverse(s_ul, boost_eps)
            yv = -mm(l_ul, yv)
            return (sinvj, sinv_ul, yw, yv), (sinvj, lj)

        dpr = dp[::-1]
        xs = (
            dp[1:], ep[1:], fp[:-1],
            _flip2(dpr[1:]),          # d_r[j]   = flip2(d[M-1-j])
            _flip2(fp[::-1][1:]),     # e_r[j]   = flip2(f[M-1-j])
            _flip2(ep[::-1][:-1]),    # f_r[j-1] = flip2(e[M-j])
        )
        init = (sinv0, sinv_ul0, cqp, _fliprows(bqp))
        (sinv_l, sinv_ul_l, yw_l, yv_l), (sinv_rest, l_rest) = jax.lax.scan(
            step, init, xs
        )
        sinv = jnp.concatenate([sinv0[None], sinv_rest], axis=0)
        l = jnp.concatenate([jnp.zeros_like(sinv0)[None], l_rest], axis=0)
        vb = mm(sinv_l, bqp)
        wb = mm(sinv_l, yw_l)
        wt = _fliprows(mm(sinv_ul_l, _fliprows(cqp)))
        vt = _fliprows(mm(sinv_ul_l, yv_l))
        return sinv, l, vb, vt, wt, wb

    return jax.vmap(one_partition)(d, e, f, bq, cq)


def pad_couplings(
    b_cpl: jax.Array, c_cpl: jax.Array, p: int
) -> Tuple[jax.Array, jax.Array]:
    """(P-1, K, K) interface couplings -> per-partition (P, K, K) layout.

    ``bq[p] = B_p`` (zero for the last partition, which has no right
    neighbor); ``cq[p] = C_p`` (zero for the first).  Zero couplings make
    the corresponding corner blocks exactly zero, so padded slots carry no
    information and slicing recovers the interface layout.
    """
    pad = jnp.zeros(b_cpl.shape[:-3] + (1,) + b_cpl.shape[-2:], b_cpl.dtype)
    bq = jnp.concatenate([b_cpl, pad], axis=-3)
    cq = jnp.concatenate([pad, c_cpl], axis=-3)
    return bq, cq


def fused_factor_spike_ref(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    b_cpl: jax.Array,
    c_cpl: jax.Array,
    boost_eps: float = DEFAULT_BOOST,
) -> FusedSpikeFactors:
    """Fused factor + spike-corner extraction (pure-jnp reference).

    d/e/f: (P, M, K, K) partition blocks; b_cpl/c_cpl: (P-1, K, K)
    interface couplings as in :class:`~repro.core.banded.BlockTridiag`.
    ``lu``, ``v_bot`` and ``w_top`` are bit-identical to the
    btf/UL-sequence formulation (:func:`btf_ref` /
    :func:`btf_ul_ref`); ``v_top`` / ``w_bot`` are algebraically equal to
    the whole-spike bts solves but computed through the UL/LU forward
    carries instead (different rounding).
    """
    p = d.shape[0]
    bq, cq = pad_couplings(b_cpl.astype(d.dtype), c_cpl.astype(d.dtype), p)
    sinv, l, vb, vt, wt, wb = fused_factor_spike_padded_ref(
        d, e, f, bq, cq, boost_eps
    )
    return FusedSpikeFactors(
        lu=BTFactors(sinv=sinv, l=l, f=f),
        v_bot=vb[:-1],
        v_top=vt[:-1],
        w_top=wt[1:],
        w_bot=wb[1:],
    )
