"""Misconvergence regression suite: the converged-but-wrong solve is dead.

PR 6 left an open item: pow2 bucketing K=3 -> 4 inserted a structurally
zero outer diagonal, `boost_eps` regularized the resulting singular
coupling blocks, and the solver reported ``converged=True`` on the
preconditioned residual while the TRUE residual sat at ~1e-2.  This file
pins the three layers of the fix:

  * the interleaved identity-row K-padding embeds a K-rounded band as an
    exact (permuted) blkdiag(A, I) system -- property-tested across
    variants C/D/E and both generators (run under ``JAX_ENABLE_X64`` in
    CI for the strict oscillatory d<1 cases);
  * ``gj_inverse`` never boosts structurally-zero pivot rows;
  * ``true_resnorm`` is populated on the single, batched, and served
    paths, and the serving guard escalates a converged-but-wrong solve
    instead of returning it.

No test here pins K to the bucket K -- the whole point is that K
rounding no longer needs a workaround.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SaPOptions,
    batch_factor,
    batch_plan,
    bucket_shape,
    factor,
    gj_inverse,
    pad_band_to,
    pad_permutation,
    pad_rhs_to,
    plan_banded,
    solve_banded,
    unpad_solution,
)
from repro.core.banded import (
    band_to_dense,
    oscillatory_banded,
    random_banded,
)
from repro.serve.solver_engine import SolverEngine
from repro.serve.service import AsyncSolverService

X64 = jax.config.jax_enable_x64
FDTYPE = jnp.float64 if X64 else jnp.float32
# the preconditioner runs in f32 by default; under x64 the strict
# tolerances below need the f64 preconditioner as well
PKW = {"precond_dtype": "float64"} if X64 else {}


def _true_res(band, x, b):
    A = np.asarray(band_to_dense(jnp.asarray(band)), np.float64)
    b = np.asarray(b, np.float64)
    return np.linalg.norm(b - A @ np.asarray(x, np.float64)) / np.linalg.norm(b)


def _band(gen, n, k, d, seed):
    if gen == "oscillatory":
        return np.asarray(oscillatory_banded(n, k, d=d, seed=seed), FDTYPE)
    return np.asarray(random_banded(n, k, d=d, seed=seed), FDTYPE)


# ---------------------------------------------------------------------------
# the PR 6 repro, un-pinned
# ---------------------------------------------------------------------------


def test_pr6_repro_oscillatory_k3_pow2_bucket_variant_e():
    """Oscillatory d<1 band, K=3 pow2-bucketed to 4, variant E: converges
    with true_resnorm <= tol (the old code plateaued at ~1e-2)."""
    tol = 1e-10 if X64 else 1e-5
    band = _band("oscillatory", 128, 3, 0.5, seed=0)
    rng = np.random.default_rng(1)
    b = np.asarray(rng.normal(size=128), FDTYPE)
    opts = SaPOptions(p=4, variant="E", tol=tol, maxiter=400, **PKW)
    bpl = batch_plan([band], opts, rounding="pow2")
    assert bpl.k == 4 and bpl.orig_ks == (3,)  # K actually rounded
    bfac = batch_factor(bpl)
    res = bfac.solve_batch(pad_rhs_to(jnp.asarray(b), bpl.n)[None])
    assert bool(np.asarray(res.converged).all())
    (x,) = unpad_solution(res.x, bpl.orig_ns)
    assert _true_res(band, x, b) <= tol
    # and the result object agrees with the from-scratch computation
    assert float(res.true_resnorm[0]) <= tol


# ---------------------------------------------------------------------------
# padding exactness, property-style sweep (C/D/E x generators x shapes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", ["C", "D", "E"])
@pytest.mark.parametrize(
    "gen,d", [("random", 1.2), ("oscillatory", 0.5)]
)
@pytest.mark.parametrize("n,k,seed", [(96, 3, 0), (130, 6, 1), (200, 5, 2)])
def test_k_and_n_rounded_embedding_is_algebraically_exact(
    variant, gen, d, n, k, seed
):
    """The padded system's exact solution restricts to the unpadded
    system's exact solution -- checked in float64 linear algebra, so this
    is a statement about the *embedding*, not about Krylov accuracy."""
    if variant in ("C", "D") and d < 1:
        pytest.skip("truncated variants are not expected to be exact at d<1")
    band = _band(gen, n, k, d, seed)
    nb, kb, _ = bucket_shape(n, k, 4, "pow2")
    assert nb > n and kb > k  # both axes actually round for these shapes
    padded = pad_band_to(jnp.asarray(band), nb, kb)
    dense = np.asarray(band_to_dense(jnp.asarray(band)), np.float64)
    dense_p = np.asarray(band_to_dense(padded), np.float64)
    rng = np.random.default_rng(seed + 7)
    b = rng.normal(size=n)
    bp = np.zeros(nb)
    perm = pad_permutation(n, k, nb, kb)
    assert perm is not None
    bp[perm[:n]] = b  # RHS in the interleaved frame
    xp = np.linalg.solve(dense_p, bp)
    x = np.linalg.solve(dense, b)
    np.testing.assert_allclose(xp[perm[:n]], x, rtol=1e-9, atol=1e-9)
    # padded slots stay exactly zero: identity rows with zero RHS
    mask = np.ones(nb, bool)
    mask[perm[:n]] = False
    np.testing.assert_array_equal(xp[mask], 0.0)


@pytest.mark.parametrize("variant", ["C", "D", "E"])
def test_solver_matches_unpadded_through_k_rounding(variant):
    """End-to-end: the batched solve through a K-rounding bucket agrees
    with the standalone unpadded solve of each system."""
    d = 1.2  # all three variants converge here; E is also exercised at
    # d<1 by the PR 6 repro test above
    tol = 1e-10 if X64 else 1e-6
    opts = SaPOptions(p=4, variant=variant, tol=tol, maxiter=400, **PKW)
    bands = [_band("random", 96, 3, d, s) for s in range(3)]
    rng = np.random.default_rng(11)
    bs = [np.asarray(rng.normal(size=96), FDTYPE) for _ in bands]
    bpl = batch_plan(bands, opts, rounding="pow2")
    assert bpl.k > 3
    bfac = batch_factor(bpl)
    res = bfac.solve_batch(
        jnp.stack([pad_rhs_to(jnp.asarray(b), bpl.n) for b in bs])
    )
    assert bool(np.asarray(res.converged).all())
    xs = unpad_solution(res.x, bpl.orig_ns)
    for band, b, x in zip(bands, bs, xs):
        solo = factor(plan_banded(jnp.asarray(band), opts)).solve(
            jnp.asarray(b)
        )
        assert _true_res(band, x, b) < 100 * tol
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(solo.x),
            rtol=1e-8 if X64 else 1e-3, atol=1e-8 if X64 else 1e-4,
        )


# ---------------------------------------------------------------------------
# gj_inverse: structural zeros are never boosted
# ---------------------------------------------------------------------------


def _live_block(k, seed):
    """d = 1 dominant (K, K) block, U(-1, 1) off the diagonal."""
    a = np.random.default_rng(seed).uniform(-1.0, 1.0, size=(k, k))
    np.fill_diagonal(a, 0.0)
    np.fill_diagonal(a, np.abs(a).sum(axis=1))
    return a


# K = 5 is one pivot loop; K = 200 is inverted by the blocked recursion,
# split at h = 128, so the padded slots sit on both sides of the split
@pytest.mark.parametrize("k,pad,h", [(5, (3, 4), 3),
                                     (200, (0, 61, 127, 128, 170, 199), 128)])
def test_gj_inverse_identity_on_structurally_zero_rows(k, pad, h):
    """A block whose rows/cols ``pad`` are identity-padded inverts to
    the inverse of the live block plus identity slots -- no 1/boost_eps
    garbage in the padded rows.  A row that is zero left of ``h`` but not
    right of it is boosted like any other, and singular blocks stay
    finite."""
    pad = list(pad)
    live = np.setdiff1d(np.arange(k), pad)
    # a normal block of 194 rows would need pivoting: take the dominant law
    a_live = (_live_block(live.size, seed=3) if k > 128 else
              np.random.default_rng(3).normal(size=(live.size, live.size)))
    blk = np.zeros((k, k))
    blk[np.ix_(live, live)] = a_live
    # structurally zero rows (identity-slot semantics)
    inv = np.asarray(gj_inverse(jnp.asarray(blk, FDTYPE), boost_eps=1e-10))
    np.testing.assert_allclose(
        inv[np.ix_(live, live)], np.linalg.inv(a_live), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_array_equal(inv[np.ix_(pad, live)], 0.0)
    np.testing.assert_array_equal(inv[np.ix_(live, pad)], 0.0)
    np.testing.assert_array_equal(inv[np.ix_(pad, pad)], np.eye(len(pad)))
    # numerically small but structurally nonzero pivots still boost
    diag = np.ones(k)
    diag[-1] = 1e-30
    inv_t = np.asarray(gj_inverse(jnp.asarray(np.diag(diag), FDTYPE),
                                  boost_eps=1e-10))
    assert np.isfinite(inv_t).all() and inv_t[-1, -1] < 1e12
    # row 1 is zero left of h (its pivot too) but not right of it: the
    # whole row is not zero, so its pivot is boosted to thr, not taken as 1
    a = _live_block(k, seed=4)
    a[1, :h] = 0.0
    eps = 1e-3
    thr = eps * np.abs(a).max()
    bump = np.zeros((k, k))
    bump[1, 1] = 1.0
    inv_b = np.asarray(gj_inverse(jnp.asarray(a, FDTYPE), boost_eps=eps))
    boosted = np.linalg.inv(a + thr * bump)
    scale = np.abs(boosted).max()
    np.testing.assert_allclose(inv_b, boosted, rtol=0, atol=1e-5 * scale)
    assert np.abs(inv_b - np.linalg.inv(a + bump)).max() > 0.1 * scale
    # singular blocks: every row equal, or one row repeated across h
    dup = _live_block(k, seed=5)
    dup[-1] = dup[1]
    for sing in (np.ones((k, k)), dup):
        inv_s = gj_inverse(jnp.asarray(sing, FDTYPE), boost_eps=1e-10)
        assert bool(jnp.all(jnp.isfinite(inv_s)))


# ---------------------------------------------------------------------------
# true_resnorm is populated on every path
# ---------------------------------------------------------------------------


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_true_resnorm_on_single_batched_and_served_paths():
    band = _band("random", 128, 3, 1.2, seed=4)
    rng = np.random.default_rng(5)
    b = np.asarray(rng.normal(size=128), FDTYPE)
    opts = SaPOptions(p=4, variant="C", tol=1e-6, maxiter=300)

    res1 = factor(plan_banded(jnp.asarray(band), opts)).solve(  # single
        jnp.asarray(b)
    )
    assert res1.true_resnorm is not None
    assert abs(float(res1.true_resnorm) - _true_res(band, res1.x, b)) < 1e-4
    sol = solve_banded(jnp.asarray(band), jnp.asarray(b), opts)
    assert np.isfinite(sol.true_resnorm)  # convenience-wrapper float field

    bpl = batch_plan([band], opts, rounding="pow2")  # batched
    res = batch_factor(bpl).solve_batch(
        pad_rhs_to(jnp.asarray(b), bpl.n)[None]
    )
    assert res.true_resnorm is not None
    assert np.isfinite(float(res.true_resnorm[0]))

    eng = SolverEngine(opts)  # served
    eng.submit_system(band, b)
    (done,) = eng.step()
    assert np.isfinite(done.result.true_resnorm)
    assert done.result.true_resnorm < 1e-3


# ---------------------------------------------------------------------------
# the serving guard: detect, escalate, never lie
# ---------------------------------------------------------------------------


def _wide_stored_oscillatory(n=128, k_true=3, k_stored=4, seed=1):
    """The user-side twin of the bucketing bug: a K=3 matrix submitted in
    K=4 band storage (exactly-zero outer diagonals).  k == bucket K, so
    no interleave kicks in and the first pass misconverges like PR 6."""
    band3 = np.asarray(oscillatory_banded(n, k_true, d=0.5, seed=seed),
                       FDTYPE)
    wide = np.zeros((n, 2 * k_stored + 1), FDTYPE)
    pad = k_stored - k_true
    wide[:, pad: 2 * k_true + 1 + pad] = band3
    rng = np.random.default_rng(seed + 10)
    x = rng.normal(size=n)
    b = np.asarray(band_to_dense(jnp.asarray(band3)), np.float64) @ x
    return wide, np.asarray(b, FDTYPE)


def test_engine_guard_escalates_converged_but_wrong_solve():
    # the f32 preconditioner in BOTH precision configs: misconvergence is
    # an f32-precond phenomenon, and the guard must catch it there
    tol = 1e-5
    wide, b = _wide_stored_oscillatory()
    eng = SolverEngine(
        SaPOptions(p=4, variant="E", tol=tol, maxiter=400),
        rounding="pow2",
    )
    eng.submit_system(wide, b)
    (done,) = eng.step()
    r = done.result
    assert r.escalated  # the first pass tripped the guard
    assert r.converged
    assert r.true_resnorm <= 10 * tol  # escalation actually fixed it
    assert _true_res(wide, r.x, b) <= 10 * tol
    assert eng.stats["misconverged"] >= 1
    assert eng.stats["escalations"] >= 1


def test_check_true_residual_opt_sets_the_guard():
    """An explicit opts.check_true_residual overrides the 10*tol default:
    a huge guard accepts the first (wrong) pass without escalating."""
    wide, b = _wide_stored_oscillatory()
    eng = SolverEngine(
        SaPOptions(p=4, variant="E", tol=1e-5, maxiter=400,
                   check_true_residual=1e3),
        rounding="pow2",
    )
    eng.submit_system(wide, b)
    (done,) = eng.step()
    assert not done.result.misconverged and not done.result.escalated
    assert eng.stats["escalations"] == 0


def test_service_exports_misconvergence_counters():
    wide, b = _wide_stored_oscillatory(seed=2)
    svc = AsyncSolverService(
        SaPOptions(p=4, variant="E", tol=1e-5, maxiter=400),
        rounding="pow2", start=False,
    )
    fut = svc.submit(wide, b)
    svc.drain_once()
    out = fut.result(timeout=0)
    assert out.escalated and out.converged
    snap = svc.snapshot()
    assert snap["counters"]["misconverged_total"] >= 1
    assert snap["counters"]["escalations"] >= 1
    svc.close()
