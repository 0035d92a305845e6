"""Unit tests: BiCGStab(2) and CG solvers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.krylov import (
    KrylovResult,
    _bicgstab2_impl,
    _dot,
    _identity,
    _true_resnorm,
    bicgstab2,
    bicgstab2_applies,
    cg,
)


def _random_system(n=50, seed=0, spd=False):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(n, n))
    if spd:
        a = a @ a.T + n * np.eye(n)
    else:
        a = a + n * np.eye(n)  # well-conditioned, nonsymmetric
    x = rng.normal(size=n)
    return a, x, a @ x


def test_bicgstab2_unpreconditioned():
    a, xstar, b = _random_system(seed=1)
    res = bicgstab2(lambda v: jnp.asarray(a) @ v, jnp.asarray(b), tol=1e-8,
                    maxiter=200)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), xstar, rtol=1e-4, atol=1e-5)


def test_bicgstab2_exact_preconditioner_quarter_exit():
    """With M = A^{-1} the solver must exit in <= 1 iteration and must NOT
    poison x (regression test for the MR degeneracy guard)."""
    a, xstar, b = _random_system(seed=2)
    ainv = jnp.asarray(np.linalg.inv(a))
    res = bicgstab2(
        lambda v: jnp.asarray(a) @ v,
        jnp.asarray(b),
        precond=lambda v: ainv @ v,
        tol=1e-5,
        maxiter=50,
    )
    assert bool(res.converged)
    assert float(res.iterations) <= 1.0
    np.testing.assert_allclose(np.asarray(res.x), xstar, rtol=1e-3, atol=1e-4)


def test_bicgstab2_counts_quarters():
    a, xstar, b = _random_system(seed=3)
    ainv = jnp.asarray(np.linalg.inv(a))
    res = bicgstab2(lambda v: jnp.asarray(a) @ v, jnp.asarray(b),
                    precond=lambda v: ainv @ v, tol=1e-5)
    assert float(res.iterations) in (0.0, 0.25, 0.5, 1.0)


def test_cg_spd():
    a, xstar, b = _random_system(seed=4, spd=True)
    res = cg(lambda v: jnp.asarray(a) @ v, jnp.asarray(b), tol=1e-10,
             maxiter=500)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), xstar, rtol=1e-4, atol=1e-5)


def test_cg_jacobi_preconditioner_helps():
    rng = np.random.default_rng(5)
    d = np.abs(rng.normal(size=60)) * 100 + 1
    a = np.diag(d) + rng.normal(size=(60, 60)) * 0.1
    a = (a + a.T) / 2 + 10 * np.eye(60)
    b = rng.normal(size=60)
    plain = cg(lambda v: jnp.asarray(a) @ v, jnp.asarray(b), tol=1e-10,
               maxiter=500)
    jac = cg(
        lambda v: jnp.asarray(a) @ v,
        jnp.asarray(b),
        precond=lambda v: v / jnp.asarray(np.diag(a)),
        tol=1e-10,
        maxiter=500,
    )
    assert bool(jac.converged)
    assert float(jac.iterations) <= float(plain.iterations)


def test_bicgstab2_zero_rhs():
    a, _, _ = _random_system(seed=6)
    res = bicgstab2(lambda v: jnp.asarray(a) @ v, jnp.zeros(50), tol=1e-10)
    assert bool(res.converged)
    assert float(jnp.abs(res.x).max()) == 0.0


def test_bicgstab2_maxiter_respected():
    # nearly singular, no preconditioner, tiny budget
    rng = np.random.default_rng(7)
    a = rng.normal(size=(80, 80))
    b = rng.normal(size=80)
    res = bicgstab2(lambda v: jnp.asarray(a) @ v, jnp.asarray(b), tol=1e-14,
                    maxiter=3)
    assert float(res.iterations) <= 3.0


# ---------------------------------------------------------------------------
# The one-apply-per-trip loop against whole sweeps
# ---------------------------------------------------------------------------


def _full_sweep_bicgstab2(
    matvec, b, precond=_identity, x0=None, tol=1e-10, maxiter=500,
    record_history=False,
):
    """Oracle: BiCGStab(2) with all four applies of a sweep in one loop
    body, choosing the quarter-exit snapshot afterwards."""
    dtype = b.dtype
    op = lambda v: precond(matvec(v)).astype(dtype)

    x = jnp.zeros_like(b) if x0 is None else x0
    r0 = precond(b - matvec(x)).astype(dtype)
    bnorm = jnp.linalg.norm(precond(b).astype(dtype))
    bnorm = jnp.where(bnorm > 0, bnorm, 1.0)
    rtilde = r0
    eps = jnp.asarray(1e-300 if dtype == jnp.float64 else 1e-30, dtype)

    def cond(state):
        (x, r, u, rho, omega, alpha, it, done) = state[:8]
        return (~done) & (it < maxiter)

    def _select(c, a, b):
        return jax.tree.map(lambda p, q: jnp.where(c, p, q), a, b)

    def body(state):
        (x, r0, u0, rho0, omega, alpha, it, done) = state[:8]
        sweep_idx = it.astype(jnp.int32)
        rho0 = -omega * rho0

        rho1 = _dot(r0, rtilde)
        beta = jnp.where(jnp.abs(rho0) > eps, alpha * rho1 / rho0, 0.0)
        rho0 = rho1
        u0 = r0 - beta * u0
        u1 = op(u0)
        gamma = _dot(u1, rtilde)
        alpha = jnp.where(jnp.abs(gamma) > eps, rho0 / gamma, 0.0)
        r0 = r0 - alpha * u1
        r1 = op(r0)
        x = x + alpha * u0
        q1 = jnp.linalg.norm(r0) <= tol * bnorm
        snap1 = (x, r0, u0, rho0, omega, alpha, it + 0.25, q1)

        rho1 = _dot(r1, rtilde)
        beta = jnp.where(jnp.abs(rho0) > eps, alpha * rho1 / rho0, 0.0)
        rho0 = rho1
        u0 = r0 - beta * u0
        u1 = r1 - beta * u1
        u2 = op(u1)
        gamma = _dot(u2, rtilde)
        alpha = jnp.where(jnp.abs(gamma) > eps, rho0 / gamma, 0.0)
        r0 = r0 - alpha * u1
        r1 = r1 - alpha * u2
        r2 = op(r1)
        x = x + alpha * u0
        q2 = jnp.linalg.norm(r0) <= tol * bnorm
        snap2 = (x, r0, u0, rho0, omega, alpha, it + 0.5, q2)

        sigma1 = jnp.maximum(_dot(r1, r1), eps)
        gp1 = _dot(r0, r1) / sigma1
        tau12 = _dot(r2, r1) / sigma1
        r2o = r2 - tau12 * r1
        sigma2 = _dot(r2o, r2o)
        ratio_eps = jnp.asarray((50 * jnp.finfo(dtype).eps) ** 2, dtype)
        degenerate = sigma2 <= ratio_eps * sigma1
        gp2 = jnp.where(
            degenerate, 0.0, _dot(r0, r2o) / jnp.maximum(sigma2, eps)
        )
        g2 = gp2
        omega_new = jnp.where(degenerate, gp1, g2)
        g1 = gp1 - tau12 * g2
        gpp1 = g2

        x = x + g1 * r0 + gpp1 * r1
        r0 = r0 - gp1 * r1 - gp2 * r2o
        u0 = u0 - g1 * u1 - g2 * u2

        q4 = jnp.linalg.norm(r0) <= tol * bnorm
        full = (x, r0, u0, rho0, omega_new, alpha, it + 1.0, q4)
        new = _select(q1, snap1, _select(q2, snap2, full))
        if record_history:
            hist = state[8].at[sweep_idx].set(jnp.linalg.norm(new[1]) / bnorm)
            return new + (hist,)
        return new

    state = (
        x, r0, jnp.zeros_like(b),
        jnp.asarray(1.0, dtype), jnp.asarray(1.0, dtype),
        jnp.asarray(0.0, dtype), jnp.asarray(0.0, dtype),
        jnp.linalg.norm(r0) <= tol * bnorm,
    )
    if record_history:
        state = state + (jnp.full((maxiter,), jnp.nan, dtype),)
    out = jax.lax.while_loop(cond, body, state)
    (x, r, _, _, _, _, it, done) = out[:8]
    return KrylovResult(
        x=x,
        iterations=it,
        resnorm=jnp.linalg.norm(r) / bnorm,
        converged=done,
        true_resnorm=_true_resnorm(matvec, b, x),
        history=out[8] if record_history else None,
    )


def _perturbed_inverse(a, pert, seed):
    """An inexact preconditioner: the inverse of A plus a relative
    perturbation ``pert`` -- 0 gives the exact inverse (quarter exits),
    larger values whole sweeps."""
    rng = np.random.default_rng(seed)
    e = rng.normal(size=a.shape) * np.abs(a).mean() * 10
    return np.linalg.inv(a + pert * e)


# (perturbation, tol, maxiter, x0 given, record_history, iterations at exit)
PARITY = {
    "quarter": (0.0, 1e-5, 50, False, False, 0.25),
    "half": (1e-3, 1e-3, 50, False, False, 0.5),
    "sweep": (1e-3, 1e-4, 50, False, False, 1.0),
    "sweeps": (0.2, 1e-5, 50, False, False, 4.25),
    "maxiter": (0.3, 1e-14, 3, False, False, 3.0),
    "x0": (0.1, 1e-5, 50, True, False, None),
    "history": (0.1, 1e-5, 50, False, True, 2.5),
}
# lanes of one vmapped batch, each its own preconditioner: at tol 1e-3
# they leave at 0.25, 0.5, 1.0, 1.5 and 5.0
LANE_PERTS = (0.0, 1e-3, 0.03, 0.1, 0.3)


def _lane(pert, x0_given=False):
    a, _, b = _random_system(seed=0)
    p = _perturbed_inverse(a, pert, seed=0)
    A, P = jnp.asarray(a, jnp.float32), jnp.asarray(p, jnp.float32)
    x0 = jnp.asarray(np.linalg.solve(a, b) * 0.9, jnp.float32) if x0_given else None
    return A, P, jnp.asarray(b, jnp.float32), x0


def _assert_same(got, want):
    for field in ("x", "iterations", "resnorm", "converged", "true_resnorm",
                  "history"):
        g, w = getattr(got, field), getattr(want, field)
        if w is None:
            assert g is None, field
            continue
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=field)


@pytest.mark.parametrize("case", [*PARITY, "vmap"])
def test_bicgstab2_matches_full_sweep(case):
    """Bit for bit the full-sweep body: every exit quarter, several sweeps,
    maxiter, x0, history, and a vmapped batch whose lanes leave at
    different quarters."""

    def solve(impl, A, P, b, x0, tol, maxiter, hist):
        return impl(lambda v: A @ v, b, lambda v: P @ v, x0, tol, maxiter, hist)

    if case == "vmap":
        lanes = [_lane(pt) for pt in LANE_PERTS]
        A, P, b = (jnp.stack([ln[i] for ln in lanes]) for i in range(3))
        run = lambda impl: jax.jit(jax.vmap(
            lambda A, P, b: solve(impl, A, P, b, None, 1e-3, 50, True)
        ))(A, P, b)
        got, want = run(_bicgstab2_impl), run(_full_sweep_bicgstab2)
        _assert_same(got, want)
        assert np.asarray(got.iterations).tolist() == [0.25, 0.5, 1.0, 1.5, 5.0]
        return
    pert, tol, maxiter, x0_given, hist, exit_at = PARITY[case]
    A, P, b, x0 = _lane(pert, x0_given)
    run = lambda impl: jax.jit(
        lambda A, P, b, x0: solve(impl, A, P, b, x0, tol, maxiter, hist)
    )(A, P, b, x0)
    got, want = run(_bicgstab2_impl), run(_full_sweep_bicgstab2)
    _assert_same(got, want)
    if exit_at is not None:
        assert float(got.iterations) == exit_at
    if hist:
        h = np.asarray(got.history)
        assert np.count_nonzero(~np.isnan(h)) == np.ceil(float(got.iterations))


def _counted(calls, name, f):
    """``f`` with a host callback that counts its executions (once per
    batched call under vmap: the callback takes no batched operand)."""

    def g(v):
        jax.debug.callback(lambda: calls.__setitem__(name, calls[name] + 1))
        return f(v)

    return g


# (perturbation, tol, x0 given, iterations at exit, applies = matvecs):
# a quarter exit costs 2 of each, each further whole sweep 4 more, x0 1 more
COUNTS = {
    "quarter": (0.0, 1e-5, False, 0.25, 2),
    "quarter_x0": (0.0, 1e-5, True, 0.25, 3),
    "sweeps": (0.2, 1e-5, False, 4.25, 2 + 4 * 4),
    "sweeps_x0": (0.2, 1e-6, True, 4.25, 3 + 4 * 4),
}


@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmap"])
@pytest.mark.parametrize("case", list(COUNTS))
def test_bicgstab2_runs_only_the_applies_it_uses(case, batched):
    """Count the M^-1 and A executions with a host callback, and check
    bicgstab2_applies against them: under vmap, at the slowest lane."""
    pert, tol, x0_given, exit_at, expected = COUNTS[case]
    A, P, b, x0 = _lane(pert, x0_given)
    calls = {"A": 0, "M": 0}
    mv = _counted(calls, "A", lambda v: A @ v)
    if batched:
        # the case's lane beside lanes with the exact inverse, which leave
        # at 0.25: the batch runs as long as its slowest lane
        exact = jnp.asarray(np.linalg.inv(np.asarray(A, np.float64)), jnp.float32)
        lane_p = jnp.stack([exact, P, exact])
        scale = jnp.asarray([1.0, 0.5, 2.0], jnp.float32)
        B = b[None] * scale[:, None]
        X0 = None if x0 is None else x0[None] * scale[:, None]

        def one(p, b, x0):
            pc = _counted(calls, "M", lambda v: p @ v)
            return _bicgstab2_impl(mv, b, pc, x0, tol, 50)

        res = jax.jit(jax.vmap(one, in_axes=(0, 0, None if X0 is None else 0)))(
            lane_p, B, X0)
    else:
        pc = _counted(calls, "M", lambda v: P @ v)
        res = jax.jit(lambda b, x0: _bicgstab2_impl(mv, b, pc, x0, tol, 50))(b, x0)
    jax.effects_barrier()
    its = np.asarray(res.iterations)
    assert float(its.max()) == exit_at
    assert bool(np.all(np.asarray(res.converged)))
    assert calls == {"A": expected, "M": expected}
    assert bicgstab2_applies(its.max(), x0_given=x0_given) == expected


def test_bicgstab2_applies_table():
    assert [bicgstab2_applies(q) for q in (0.0, 0.25, 0.5, 1.0, 1.25, 2.5)] == [
        1, 2, 4, 5, 6, 12,
    ]
    assert bicgstab2_applies(0.25, x0_given=True) == 3
    assert bicgstab2_applies(np.float32(3.0)) == 13
