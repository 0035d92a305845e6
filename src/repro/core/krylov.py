"""Krylov-subspace solvers: BiCGStab(2) and CG, left-preconditioned.

Paper Sec. 2.1.1: the SaP preconditioner (coupled or decoupled) is wrapped
in BiCGStab(l) [Sleijpen & Fokkema 1993] with l = 2, or CG when the matrix
is symmetric positive definite.  Following the paper's convention, BiCGStab
iterations are counted in *quarters* (the algorithm has intermediate exit
points); we track them the same way so benchmark tables line up with
Tables 4.1 / 4.2.

Mixed precision (paper Sec. 3.1): the preconditioner apply runs in its own
(lower) storage dtype; the outer iteration runs in the dtype of ``b``.

``matvec`` / ``precond`` may be plain callables or anything exposing a
``.matvec`` method (a :class:`repro.core.operators.LinearOperator`).
Multi-RHS systems use :func:`bicgstab2_many` / :func:`cg_many`, which vmap
the solver over a trailing batch axis of ``b`` -- each column converges
independently (converged columns freeze while stragglers iterate).

Everything is expressed with ``jax.lax.while_loop`` so it stays on-device
and can be jitted / sharded.  The underscore ``_*_impl`` variants are the
unjitted bodies, for embedding inside an enclosing jit (e.g. the
``SaPFactorization.solve`` path) without nested-jit cache churn.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Union

import jax
import jax.numpy as jnp

from .operators import LinearOperator, as_matvec

MatVec = Union[Callable[[jax.Array], jax.Array], LinearOperator]


class KrylovResult(NamedTuple):
    """Solver exit state.

    ``converged``/``resnorm`` report the *preconditioned* residual the
    iteration actually controls (``M^-1 (b - A x)`` under left
    preconditioning): that is what ``tol`` bounds, and a strong but
    *inexact* preconditioner can meet it while ``b - A x`` is still
    large.  ``true_resnorm`` is the unpreconditioned check
    ``||b - A x|| / ||b||``, recomputed from scratch at exit (one extra
    matvec) -- the quantity callers should trust.
    """

    x: jax.Array
    iterations: jax.Array  # fractional iterations (quarters for BiCGStab)
    resnorm: jax.Array  # preconditioned residual norm at exit
    converged: jax.Array
    true_resnorm: jax.Array | None = None  # ||b - A x|| / ||b||
    # With record_history=True: (maxiter,) preconditioned relative residual
    # after each outer sweep, NaN-padded past the exit sweep.  The number of
    # non-NaN entries is ceil(iterations) (BiCGStab quarter-exits record the
    # sweep they exit from); entry i is the residual the convergence test saw
    # at the end of sweep i.  None when history was not requested.
    history: jax.Array | None = None


def _true_resnorm(matvec, b, x) -> jax.Array:
    """Unpreconditioned relative residual, recomputed (not the recurrence)."""
    bn = jnp.linalg.norm(b)
    bn = jnp.where(bn > 0, bn, 1.0)
    return jnp.linalg.norm(b - matvec(x).astype(b.dtype)) / bn


def _identity(x):
    return x


def _dot(a, b):
    return jnp.sum(a * b)


# ---------------------------------------------------------------------------
# BiCGStab(2)  (Sleijpen & Fokkema), left preconditioning: solve M^-1 A x = M^-1 b
# ---------------------------------------------------------------------------


def bicgstab2_applies(iterations, x0_given: bool = False) -> int:
    """Preconditioner applies BiCGStab(2) runs for an exit at ``iterations``.

    One apply starts the loop (``r0 = M^-1 b``; with ``x0`` a second one
    gives ``||M^-1 b||``), each whole sweep takes four, and a lane that
    leaves a sweep at quarter 0.25 / 0.5 has run one / three of them.  The
    matvecs come to the same count: one per loop apply, one for ``x0``'s
    residual, and the exit's ``true_resnorm`` in place of ``r0``'s.  Under
    ``vmap`` the loop runs every lane as long as the slowest, so the count
    at the slowest lane's ``iterations`` is what the batched loop runs.
    """
    sweeps, quarter = divmod(float(iterations), 1.0)
    return (2 if x0_given else 1) + 4 * int(sweeps) + {0.0: 0, 0.25: 1, 0.5: 3}[quarter]


def _bicgstab2_impl(
    matvec: MatVec,
    b: jax.Array,
    precond: MatVec = _identity,
    x0: jax.Array | None = None,
    tol: float = 1e-10,
    maxiter: int = 500,
    record_history: bool = False,
) -> KrylovResult:
    """BiCGStab(2) with left preconditioning (unjitted body).

    One outer "iteration" (sweep, Sleijpen & Fokkema Alg. 3.1, l = 2) =
    two ``M^-1 A`` applies in the BiCG part plus two in the MR part, with
    exit points after the 1st (quarter 0.25), 3rd (0.5) and 4th (1.0)
    apply, counted in quarters to mirror the paper's tables (Sec. 4.1.1).
    Each ``while_loop`` trip runs exactly one apply, and the sweep's phase
    (0..3) rides in the loop state, so a lane leaves at the first exit it
    meets and no apply runs whose result would be thrown away -- under
    ``vmap`` the loop ends only when every lane has left it.

    ``record_history`` is a static flag: when True a fixed-size ``(maxiter,)``
    NaN-initialized residual array rides through the while_loop state and is
    returned on ``KrylovResult.history``; when False the loop carries no
    history at all.
    """
    dtype = b.dtype
    op = lambda v: precond(matvec(v)).astype(dtype)

    if x0 is None:
        # b - A 0 = b: no matvec of the zero vector, and ||M^-1 b|| is ||r0||
        x = jnp.zeros_like(b)
        r0 = precond(b).astype(dtype)
        bnorm = jnp.linalg.norm(r0)
    else:
        x = x0
        r0 = precond(b - matvec(x)).astype(dtype)
        bnorm = jnp.linalg.norm(precond(b).astype(dtype))
    bnorm = jnp.where(bnorm > 0, bnorm, 1.0)
    rtilde = r0
    eps = jnp.asarray(1e-300 if dtype == jnp.float64 else 1e-30, dtype)

    def cond(state):
        (it, done) = state[9:11]
        return (~done) & (it < maxiter)

    def _exit(r0, it, quarter):
        q = jnp.linalg.norm(r0) <= tol * bnorm
        return jnp.where(q, it + quarter, it), q

    def opening(r0, u0, rho0, omega, alpha):
        # a sweep opens (j = 0): new rho and beta, search direction u0
        rho_prev = -omega * rho0
        rho1 = _dot(r0, rtilde)
        beta = jnp.where(jnp.abs(rho_prev) > eps, alpha * rho1 / rho_prev, 0.0)
        return r0 - beta * u0, rho1

    # One branch per phase, run after that phase's apply ``w``.  `it` stays
    # whole inside a sweep and steps by the quarter the lane leaves at.
    def bicg0(w, x, r0, r1, u0, u1, u2, rho0, omega, alpha, it):
        # BiCG part, j = 0: u1 = M^-1 A u0; quarter-exit 1
        u1 = w
        gamma = _dot(u1, rtilde)
        alpha = jnp.where(jnp.abs(gamma) > eps, rho0 / gamma, 0.0)
        r0 = r0 - alpha * u1
        x = x + alpha * u0
        it, q = _exit(r0, it, 0.25)
        return (x, r0, r1, u0, u1, u2, rho0, omega, alpha, it, q)

    def bicg1(w, x, r0, r1, u0, u1, u2, rho0, omega, alpha, it):
        # BiCG part, j = 1: r1 = M^-1 A r0, new directions
        r1 = w
        rho1 = _dot(r1, rtilde)
        beta = jnp.where(jnp.abs(rho0) > eps, alpha * rho1 / rho0, 0.0)
        rho0 = rho1
        u0 = r0 - beta * u0
        u1 = r1 - beta * u1
        return (x, r0, r1, u0, u1, u2, rho0, omega, alpha, it, jnp.asarray(False))

    def bicg2(w, x, r0, r1, u0, u1, u2, rho0, omega, alpha, it):
        # u2 = M^-1 A u1; quarter-exit 2
        u2 = w
        gamma = _dot(u2, rtilde)
        alpha = jnp.where(jnp.abs(gamma) > eps, rho0 / gamma, 0.0)
        r0 = r0 - alpha * u1
        r1 = r1 - alpha * u2
        x = x + alpha * u0
        it, q = _exit(r0, it, 0.5)
        return (x, r0, r1, u0, u1, u2, rho0, omega, alpha, it, q)

    def mr(w, x, r0, r1, u0, u1, u2, rho0, omega, alpha, it):
        # r2 = M^-1 A r1; MR part (modified Gram-Schmidt on r1, r2).
        # Degeneracy guard: when the preconditioner is (near-)exact,
        # r2 - tau12 r1 is rounding noise; using it poisons x while the
        # recurrence residual stays small.  Detect via the relative norm of
        # the orthogonalized direction and fall back to the l=1 step.
        r2 = w
        sigma1 = jnp.maximum(_dot(r1, r1), eps)
        gp1 = _dot(r0, r1) / sigma1
        tau12 = _dot(r2, r1) / sigma1
        r2o = r2 - tau12 * r1
        sigma2 = _dot(r2o, r2o)
        ratio_eps = jnp.asarray(
            (50 * jnp.finfo(dtype).eps) ** 2, dtype
        )
        degenerate = sigma2 <= ratio_eps * sigma1
        gp2 = jnp.where(
            degenerate, 0.0, _dot(r0, r2o) / jnp.maximum(sigma2, eps)
        )
        g2 = gp2
        omega = jnp.where(degenerate, gp1, g2)
        g1 = gp1 - tau12 * g2
        gpp1 = g2  # gamma''_1 = gamma_2 (l = 2)

        x = x + g1 * r0 + gpp1 * r1
        r0 = r0 - gp1 * r1 - gp2 * r2o
        u0 = u0 - g1 * u1 - g2 * u2
        it = it + 1.0
        q = jnp.linalg.norm(r0) <= tol * bnorm
        return (x, r0, r1, u0, u1, u2, rho0, omega, alpha, it, q)

    def body(state):
        (x, r0, r1, u0, u1, u2, rho0, omega, alpha, it, done, phase) = state[:12]
        # `it` is whole until the lane leaves, so it doubles as the 0-based
        # history index of the sweep
        sweep_idx = it.astype(jnp.int32)
        u0, rho0 = jax.lax.cond(
            phase == 0,
            opening,
            lambda r0, u0, rho0, omega, alpha: (u0, rho0),
            r0, u0, rho0, omega, alpha,
        )
        # the one apply of this trip, on the vector the phase needs
        v = jax.lax.select_n(phase, u0, r0, u1, r1)
        w = op(v)
        new = jax.lax.switch(
            phase, (bicg0, bicg1, bicg2, mr),
            w, x, r0, r1, u0, u1, u2, rho0, omega, alpha, it,
        )
        new = new + ((phase + 1) % 4,)
        if record_history:
            # one entry per sweep: where the lane leaves it, or at its end
            write = new[10] | (phase == 3)
            hist = state[12]
            val = jnp.where(
                write, jnp.linalg.norm(new[1]) / bnorm, hist[sweep_idx]
            )
            return new + (hist.at[sweep_idx].set(val),)
        return new

    zero = jnp.zeros_like(b)
    state = (
        x,
        r0,
        zero,  # r1
        zero,  # u0
        zero,  # u1
        zero,  # u2
        jnp.asarray(1.0, dtype),  # rho0
        jnp.asarray(1.0, dtype),  # omega
        jnp.asarray(0.0, dtype),  # alpha
        jnp.asarray(0.0, dtype),  # iterations
        jnp.linalg.norm(r0) <= tol * bnorm,
        jnp.asarray(0, jnp.int32),  # phase: applies done in this sweep
    )
    if record_history:
        state = state + (jnp.full((maxiter,), jnp.nan, dtype),)
    out = jax.lax.while_loop(cond, body, state)
    (x, r) = out[:2]
    (it, done) = out[9:11]
    rnorm = jnp.linalg.norm(r)
    return KrylovResult(
        x=x,
        iterations=it,
        resnorm=rnorm / bnorm,
        converged=done,
        true_resnorm=_true_resnorm(matvec, b, x),
        history=out[12] if record_history else None,
    )


_bicgstab2_jit = jax.jit(
    _bicgstab2_impl,
    static_argnames=("matvec", "precond", "maxiter", "record_history"),
)


def bicgstab2(
    matvec: MatVec,
    b: jax.Array,
    precond: MatVec = _identity,
    x0: jax.Array | None = None,
    tol: float = 1e-10,
    maxiter: int = 500,
    record_history: bool = False,
) -> KrylovResult:
    """Jitted BiCGStab(2); accepts callables or LinearOperators."""
    return _bicgstab2_jit(
        as_matvec(matvec), b, as_matvec(precond), x0, tol, maxiter, record_history
    )


# ---------------------------------------------------------------------------
# Preconditioned CG (paper: used when A is SPD)
# ---------------------------------------------------------------------------


def _cg_impl(
    matvec: MatVec,
    b: jax.Array,
    precond: MatVec = _identity,
    x0: jax.Array | None = None,
    tol: float = 1e-10,
    maxiter: int = 1000,
    record_history: bool = False,
) -> KrylovResult:
    dtype = b.dtype
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - matvec(x)
    z = precond(r).astype(dtype)
    p = z
    rz = _dot(r, z)
    bnorm = jnp.linalg.norm(b)
    bnorm = jnp.where(bnorm > 0, bnorm, 1.0)

    def cond(state):
        (x, r, z, p, rz, it, done) = state[:7]
        return (~done) & (it < maxiter)

    def body(state):
        (x, r, z, p, rz, it, done) = state[:7]
        ap = matvec(p)
        denom = _dot(p, ap)
        alpha = jnp.where(jnp.abs(denom) > 0, rz / denom, 0.0)
        x = x + alpha * p
        r = r - alpha * ap
        z = precond(r).astype(dtype)
        rz_new = _dot(r, z)
        beta = jnp.where(jnp.abs(rz) > 0, rz_new / rz, 0.0)
        p = z + beta * p
        rnorm = jnp.linalg.norm(r)
        done = rnorm <= tol * bnorm
        new = (x, r, z, p, rz_new, it + 1.0, done)
        if record_history:
            hist = state[7].at[it.astype(jnp.int32)].set(rnorm / bnorm)
            return new + (hist,)
        return new

    state = (
        x,
        r,
        z,
        p,
        rz,
        jnp.asarray(0.0, dtype),
        jnp.linalg.norm(r) <= tol * bnorm,
    )
    if record_history:
        state = state + (jnp.full((maxiter,), jnp.nan, dtype),)
    out = jax.lax.while_loop(cond, body, state)
    (x, r, _, _, _, it, done) = out[:7]
    return KrylovResult(
        x=x,
        iterations=it,
        resnorm=jnp.linalg.norm(r) / bnorm,
        converged=done,
        true_resnorm=_true_resnorm(matvec, b, x),
        history=out[7] if record_history else None,
    )


_cg_jit = jax.jit(
    _cg_impl, static_argnames=("matvec", "precond", "maxiter", "record_history")
)


def cg(
    matvec: MatVec,
    b: jax.Array,
    precond: MatVec = _identity,
    x0: jax.Array | None = None,
    tol: float = 1e-10,
    maxiter: int = 1000,
    record_history: bool = False,
) -> KrylovResult:
    """Jitted preconditioned CG; accepts callables or LinearOperators."""
    return _cg_jit(
        as_matvec(matvec), b, as_matvec(precond), x0, tol, maxiter, record_history
    )


# ---------------------------------------------------------------------------
# Iterative refinement (mixed precision: low-dtype factor, high-dtype loop)
# ---------------------------------------------------------------------------


def _refine_impl(
    matvec: MatVec,
    b: jax.Array,
    precond: MatVec = _identity,
    x0: jax.Array | None = None,
    tol: float = 1e-10,
    maxiter: int = 500,
    record_history: bool = False,
) -> KrylovResult:
    """Preconditioned iterative refinement (Richardson iteration).

    The mixed-precision workhorse (paper Sec. 3.1 economics): ``precond``
    is a *low-precision* approximate inverse (e.g. an f32 SaP
    factorization) and the outer loop runs in the dtype of ``b`` (e.g.
    f64).  Each sweep computes the residual ``r = b - A x`` in the outer
    dtype, applies the preconditioner to get a correction, and adds it:

        x_{k+1} = x_k + M^-1 (b - A x_k)

    Convergence is linear with rate ``||I - M^-1 A||``, but -- unlike the
    Krylov loops above -- the controlled residual IS the true residual:
    ``resnorm`` and ``true_resnorm`` agree by construction, and the final
    accuracy is set by the outer dtype, not the factorization dtype.
    Requires a convergent splitting (a good enough preconditioner); for
    marginal preconditioners use BiCGStab(2) instead.
    """
    dtype = b.dtype
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - matvec(x).astype(dtype)
    bnorm = jnp.linalg.norm(b)
    bnorm = jnp.where(bnorm > 0, bnorm, 1.0)

    def cond(state):
        (x, r, it, done) = state[:4]
        return (~done) & (it < maxiter)

    def body(state):
        (x, r, it, done) = state[:4]
        # correction in the (low) preconditioner dtype, applied in `dtype`
        x = x + precond(r).astype(dtype)
        r = b - matvec(x).astype(dtype)
        rnorm = jnp.linalg.norm(r)
        done = rnorm <= tol * bnorm
        new = (x, r, it + 1.0, done)
        if record_history:
            hist = state[4].at[it.astype(jnp.int32)].set(rnorm / bnorm)
            return new + (hist,)
        return new

    state = (
        x,
        r,
        jnp.asarray(0.0, dtype),
        jnp.linalg.norm(r) <= tol * bnorm,
    )
    if record_history:
        state = state + (jnp.full((maxiter,), jnp.nan, dtype),)
    out = jax.lax.while_loop(cond, body, state)
    (x, r, it, done) = out[:4]
    rnorm = jnp.linalg.norm(r)
    return KrylovResult(
        x=x,
        iterations=it,
        resnorm=rnorm / bnorm,
        converged=done,
        # the refinement residual is already the true residual; recompute
        # anyway so the contract ("recomputed at exit") matches the others
        true_resnorm=_true_resnorm(matvec, b, x),
        history=out[4] if record_history else None,
    )


_refine_jit = jax.jit(
    _refine_impl,
    static_argnames=("matvec", "precond", "maxiter", "record_history"),
)


def refine(
    matvec: MatVec,
    b: jax.Array,
    precond: MatVec = _identity,
    x0: jax.Array | None = None,
    tol: float = 1e-10,
    maxiter: int = 500,
    record_history: bool = False,
) -> KrylovResult:
    """Jitted iterative refinement; accepts callables or LinearOperators."""
    return _refine_jit(
        as_matvec(matvec), b, as_matvec(precond), x0, tol, maxiter, record_history
    )


# ---------------------------------------------------------------------------
# Multi-RHS: vmap a single-RHS solver over a trailing batch axis of b
# ---------------------------------------------------------------------------


def _vmap_rhs(impl, default_maxiter):
    def many(
        matvec: MatVec,
        b: jax.Array,
        precond: MatVec = _identity,
        x0: jax.Array | None = None,
        tol: float = 1e-10,
        maxiter: int = default_maxiter,
        record_history: bool = False,
    ) -> KrylovResult:
        """Solve A X = B for B of shape (N, R): one Krylov run per column.

        Returns a KrylovResult with x (N, R) and per-column iterations /
        resnorm / converged of shape (R,); with ``record_history=True``,
        ``history`` is (R, maxiter) -- row r is column r's residual track.
        Unjitted: wrap in jax.jit (or call via SaPFactorization.solve_many)
        for a cached executable.
        """
        out_axes = KrylovResult(
            x=1,
            iterations=0,
            resnorm=0,
            converged=0,
            true_resnorm=0,
            history=0 if record_history else None,
        )
        mv, pc = as_matvec(matvec), as_matvec(precond)
        if x0 is None:
            fn = lambda bi: impl(mv, bi, pc, None, tol, maxiter, record_history)
            return jax.vmap(fn, in_axes=1, out_axes=out_axes)(b)
        fn = lambda bi, xi: impl(mv, bi, pc, xi, tol, maxiter, record_history)
        return jax.vmap(fn, in_axes=(1, 1), out_axes=out_axes)(b, x0)

    return many


bicgstab2_many = _vmap_rhs(_bicgstab2_impl, 500)
cg_many = _vmap_rhs(_cg_impl, 1000)
refine_many = _vmap_rhs(_refine_impl, 500)
