"""Batched many-systems lifecycle: bucketing, padding exactness, parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SaPOptions,
    batch_factor,
    batch_plan,
    bucket_by_shape,
    bucket_shape,
    factor,
    index_factorization,
    pad_band_to,
    pad_rhs_to,
    plan_banded,
    stack_factorizations,
    unpad_solution,
)
from repro.core.banded import (
    band_matvec,
    band_to_dense,
    oscillatory_banded,
    random_banded,
)


def _system(n, k, d=1.0, seed=0):
    band = jnp.asarray(random_banded(n, k, d=d, seed=seed), jnp.float32)
    rng = np.random.default_rng(seed + 100)
    x = rng.normal(size=n)
    b = band_matvec(band, jnp.asarray(x, jnp.float32))
    return band, x, b


# ---------------------------------------------------------------------------
# bucketing helpers
# ---------------------------------------------------------------------------


def test_bucket_shape_invariants():
    for n, k, p in [(100, 3, 4), (4096, 16, 8), (10_001, 7, 16), (8, 1, 2)]:
        nb, kb, pb = bucket_shape(n, k, p)
        assert nb >= n and kb >= max(k, 2) and pb == p
        assert nb % (p * kb) == 0  # bucket key IS the compiled shape
        # idempotent: a bucket maps to itself
        assert bucket_shape(nb, kb, p) == (nb, kb, p)


def test_bucket_shape_exact_vs_pow2():
    # pow2 widens K 5 -> 8; the bucket must also hold the interleaved
    # identity-row embedding: ceil(1000/5)*8 = 1600 rows -> next pow2
    assert bucket_shape(1000, 5, 4, "pow2") == (2048, 8, 4)
    nb, kb, _ = bucket_shape(1000, 5, 4, "exact")
    assert kb == 5 and nb >= 1000 and nb % (4 * 5) == 0
    with pytest.raises(ValueError):
        bucket_shape(100, 3, 4, "nope")


def test_bucket_by_shape_groups_and_order():
    shapes = [(1000, 5), (900, 6), (1024, 8), (100, 2), (1000, 5)]
    buckets = bucket_by_shape(shapes, p=4)
    # pow2 + interleave room: (1000,5)->(2048,8), (900,6)->(2048,8),
    # (1024,8)->(1024,8) (K not widened -> no interleave growth)
    assert buckets[(2048, 8, 4)] == [0, 1, 4]
    assert buckets[(1024, 8, 4)] == [2]
    assert buckets[(128, 2, 4)] == [3]
    # exact mode separates distinct shapes
    assert len(bucket_by_shape(shapes, p=4, rounding="exact")) == 4


def test_pad_band_to_rejects_shrink():
    band, _, _ = _system(64, 3)
    with pytest.raises(ValueError):
        pad_band_to(band, 32, 3)
    with pytest.raises(ValueError):
        pad_band_to(band, 64, 2)


def test_padded_system_is_exactly_embedded():
    """Identity-row/zero-column padding decouples exactly: the dense
    padded matrix is blkdiag(A, I), so its solution is [x; 0]."""
    band, xstar, b = _system(60, 4, seed=3)
    padded = pad_band_to(band, 96, 7)
    dense_p = np.asarray(band_to_dense(padded), np.float64)
    dense = np.asarray(band_to_dense(band), np.float64)
    np.testing.assert_array_equal(dense_p[:60, :60], dense)
    np.testing.assert_array_equal(dense_p[60:, :60], 0.0)
    np.testing.assert_array_equal(dense_p[:60, 60:], 0.0)
    np.testing.assert_array_equal(dense_p[60:, 60:], np.eye(36))
    xp = np.linalg.solve(dense_p, np.asarray(pad_rhs_to(b, 96), np.float64))
    np.testing.assert_allclose(xp[:60], np.linalg.solve(dense, np.asarray(b)),
                               rtol=1e-10, atol=1e-10)
    np.testing.assert_array_equal(xp[60:], 0.0)


def test_k_padded_band_is_permuted_blkdiag():
    """When the bucket widens K, pad_band_to interleaves identity rows so
    the padded dense matrix is a symmetric permutation of blkdiag(A, I) --
    no structurally-singular outer diagonal, no boosted pivots."""
    from repro.core import pad_permutation

    n, k, nb, kb = 60, 3, 128, 4
    band, _, _ = _system(n, k, seed=5)
    perm = pad_permutation(n, k, nb, kb)
    assert perm is not None  # K widened and the bucket has room
    padded = pad_band_to(band, nb, kb)
    dense_p = np.asarray(band_to_dense(padded), np.float64)
    dense = np.asarray(band_to_dense(band), np.float64)
    blk = np.eye(nb)
    blk[:n, :n] = dense
    # dense_p == P @ blk @ P^T with P the interleave row permutation
    p_mat = np.zeros((nb, nb))
    p_mat[perm, np.arange(nb)] = 1.0
    np.testing.assert_array_equal(dense_p, p_mat @ blk @ p_mat.T)
    # the padded band still only occupies |offset| <= kb diagonals
    assert padded.shape == (nb, 2 * kb + 1)


# ---------------------------------------------------------------------------
# batched lifecycle parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", ["C", "D", "E"])
def test_solve_batch_matches_per_system(variant):
    opts = SaPOptions(p=4, variant=variant, tol=1e-6, maxiter=300)
    systems = [_system(320, 5, seed=i) for i in range(4)]
    bpl = batch_plan([s[0] for s in systems], opts)
    bfac = batch_factor(bpl)
    bmat = jnp.stack([pad_rhs_to(s[2], bpl.n) for s in systems])
    res = bfac.solve_batch(bmat)
    assert bool(np.asarray(res.converged).all())
    assert res.x.shape == (4, bpl.n)
    for i, (band, xstar, b) in enumerate(systems):
        one = index_factorization(bfac, i).solve(bmat[i])
        np.testing.assert_allclose(
            np.asarray(res.x[i]), np.asarray(one.x), rtol=1e-5, atol=1e-6
        )
        err = np.linalg.norm(np.asarray(res.x[i, :320]) - xstar)
        assert err / np.linalg.norm(xstar) < 1e-3


def test_heterogeneous_nk_batch_matches_unpadded_solves():
    """Systems of different (N, K) share one bucket; each padded solve
    agrees with its standalone unpadded solve to iteration tolerance."""
    opts = SaPOptions(p=4, variant="C", tol=1e-8, maxiter=400)
    systems = [_system(200, 3, seed=0), _system(301, 5, seed=1),
               _system(256, 4, seed=2)]
    bpl = batch_plan([s[0] for s in systems], opts)
    assert bpl.orig_ns == (200, 301, 256)
    assert bpl.n >= 301 and bpl.k == 8
    bfac = batch_factor(bpl)
    res = bfac.solve_batch(
        jnp.stack([pad_rhs_to(s[2], bpl.n) for s in systems])
    )
    assert bool(np.asarray(res.converged).all())
    xs = unpad_solution(res.x, bpl.orig_ns)
    for (band, xstar, b), x in zip(systems, xs):
        solo = factor(plan_banded(band, opts)).solve(b)
        np.testing.assert_allclose(x, np.asarray(solo.x), rtol=2e-4, atol=2e-5)
        # padded rows came back exactly zero-trimmed
        assert x.shape == xstar.shape


def test_bucket_of_size_one():
    band, xstar, b = _system(320, 5)
    opts = SaPOptions(p=4, tol=1e-6, maxiter=300)
    bfac = batch_factor(batch_plan([band], opts))
    assert bfac.s == 1
    res = bfac.solve_batch(pad_rhs_to(b, bfac.n)[None])
    assert bool(np.asarray(res.converged).all())
    err = np.linalg.norm(np.asarray(res.x[0, :320]) - xstar)
    assert err / np.linalg.norm(xstar) < 1e-3


def test_solve_batch_many_matches_columns():
    opts = SaPOptions(p=4, tol=1e-6, maxiter=300)
    systems = [_system(256, 4, seed=i) for i in range(3)]
    bpl = batch_plan([s[0] for s in systems], opts)
    bfac = batch_factor(bpl)
    rng = np.random.default_rng(9)
    bmany = jnp.asarray(rng.normal(size=(3, bpl.n, 2)), jnp.float32)
    res = bfac.solve_batch_many(bmany)
    assert res.x.shape == (3, bpl.n, 2)
    assert res.iterations.shape == (3, 2)
    for j in range(2):
        col = bfac.solve_batch(bmany[:, :, j])
        np.testing.assert_allclose(
            np.asarray(res.x[:, :, j]), np.asarray(col.x), rtol=1e-5,
            atol=1e-6
        )


def test_solve_batch_shape_errors():
    band, _, b = _system(320, 5)
    bfac = batch_factor(batch_plan([band], SaPOptions(p=4)))
    with pytest.raises(ValueError, match="one RHS per system"):
        bfac.solve_batch(pad_rhs_to(b, bfac.n))  # missing system axis
    with pytest.raises(ValueError, match="solve_batch_many"):
        bfac.solve_batch_many(pad_rhs_to(b, bfac.n)[None])


def test_auto_variant_resolves_from_worst_system():
    opts = SaPOptions(p=4, variant="auto", tol=1e-5, maxiter=100)
    dominant = jnp.asarray(random_banded(256, 4, d=1.5, seed=0), jnp.float32)
    hard = jnp.asarray(oscillatory_banded(256, 4, d=0.5, seed=1), jnp.float32)
    assert batch_factor(batch_plan([dominant], opts)).variant == "C"
    # one non-dominant member drags the whole batch to the exact variant
    assert batch_factor(batch_plan([dominant, hard], opts)).variant == "E"


def test_batched_factorization_is_a_pytree():
    systems = [_system(256, 4, seed=i) for i in range(2)]
    bfac = batch_factor(
        batch_plan([s[0] for s in systems], SaPOptions(p=4, tol=1e-6))
    )
    leaves, treedef = jax.tree_util.tree_flatten(bfac)
    bfac2 = jax.tree_util.tree_unflatten(treedef, leaves)
    bmat = jnp.stack([pad_rhs_to(s[2], bfac.n) for s in systems])

    @jax.jit
    def through_jit(bf, bb):
        return bf.solve_batch(bb)

    r1 = bfac.solve_batch(bmat)
    r2 = through_jit(bfac2, bmat)
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))


def test_unstack_and_restack_copy_every_leaf_exactly():
    """The engine's one-call slice and stack are the per-leaf
    index_factorization / jnp.stack, bit for bit."""
    from repro.core.batched import unstack_factorizations

    systems = [_system(256, 4, seed=i) for i in range(3)]
    bfac = batch_factor(batch_plan([s[0] for s in systems], SaPOptions(p=4)))
    facs = unstack_factorizations(bfac)
    assert len(facs) == 3
    for i, f in enumerate(facs):
        for a, b in zip(jax.tree_util.tree_leaves(f),
                        jax.tree_util.tree_leaves(index_factorization(bfac, i))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    again = stack_factorizations(facs, bfac.orig_ns)
    assert jax.tree_util.tree_structure(again) == jax.tree_util.tree_structure(bfac)
    for a, b in zip(jax.tree_util.tree_leaves(again), jax.tree_util.tree_leaves(bfac)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stack_factorizations_rejects_mixed_buckets():
    f1 = factor(plan_banded(_system(256, 4)[0], SaPOptions(p=4)))
    f2 = factor(plan_banded(_system(128, 4)[0], SaPOptions(p=4)))
    with pytest.raises(ValueError, match="different buckets"):
        stack_factorizations([f1, f2])
    with pytest.raises(ValueError, match="at least one"):
        stack_factorizations([])


def test_batch_plan_accepts_stacked_array():
    bands = jnp.stack([_system(256, 4, seed=i)[0] for i in range(3)])
    bpl = batch_plan(bands, SaPOptions(p=4))
    assert bpl.s == 3 and bpl.orig_ns == (256, 256, 256)
    assert batch_factor(bpl).s == 3
