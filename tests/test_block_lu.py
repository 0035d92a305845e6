"""Unit tests: Gauss-Jordan with boosting, block-tridiag LU/UL factor+solve."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.banded import (
    band_to_block_tridiag,
    block_tridiag_to_dense,
    random_banded,
)
from repro.core.block_lu import (
    GJ_LEAF,
    _boost_threshold,
    _gj_leaf,
    btf_ref,
    btf_ul_ref,
    bts_ref,
    flip_block_tridiag,
    gj_inverse,
)


def test_gj_inverse_matches_numpy():
    rng = np.random.default_rng(0)
    a = rng.normal(size=(12, 12)) + 6 * np.eye(12)
    inv = np.asarray(gj_inverse(jnp.asarray(a)))
    np.testing.assert_allclose(inv, np.linalg.inv(a), rtol=1e-5, atol=1e-6)


def test_gj_inverse_pivot_boosting_no_nan():
    # singular block: plain GJ would divide by zero; boosting must not NaN
    a = jnp.zeros((6, 6)).at[0, 0].set(1.0)
    inv = gj_inverse(a, boost_eps=1e-8)
    assert bool(jnp.all(jnp.isfinite(inv)))


def _dominant_block(k, seed):
    """d = 1 dominant (K, K) block: U(-1, 1) off the diagonal and
    |a_ii| = sum_j |a_ij|, the law of the paper's dense banded test."""
    rng = np.random.default_rng(seed)
    a = rng.uniform(-1.0, 1.0, size=(k, k))
    np.fill_diagonal(a, 0.0)
    np.fill_diagonal(a, np.abs(a).sum(axis=1))
    return a


def _sequential(a):
    """One pivot loop over all K pivots: what gj_inverse runs at K <= 128."""
    return jax.jit(_gj_leaf)(a, _boost_threshold(a, 1e-10))


@pytest.mark.parametrize("k", [129, 136, 200, 256, 400])
def test_gj_inverse_blocked_matches_sequential(k):
    """Above GJ_LEAF the Schur-complement recursion inverts the same block
    as one sweep of K pivots, to the sweep's own accuracy."""
    a64 = _dominant_block(k, seed=k)
    a = jnp.asarray(a64, jnp.float32)
    inv = np.asarray(gj_inverse(a), np.float64)
    seq = np.asarray(_sequential(a), np.float64)
    exact = np.linalg.inv(np.asarray(a, np.float64))
    eye = np.eye(k)
    err_seq = np.linalg.norm(seq @ a64 - eye)
    assert np.linalg.norm(inv @ a64 - eye) <= 2 * err_seq
    scale = np.abs(exact).max()
    np.testing.assert_allclose(inv, exact, rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(inv, seq, rtol=0, atol=1e-5 * scale)


@pytest.mark.parametrize("k", [8, 100, GJ_LEAF])
def test_gj_inverse_leaf_is_the_sequential_loop(k):
    """Up to GJ_LEAF nothing changed: bit for bit the one pivot loop."""
    a = jnp.asarray(_dominant_block(k, seed=k), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(gj_inverse(a)), np.asarray(_sequential(a)))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(gj_inverse)(a)), np.asarray(_sequential(a)))


@pytest.mark.parametrize("n,k,p,r", [(60, 4, 3, 1), (96, 8, 2, 5), (70, 5, 7, 2)])
def test_block_lu_solves_partition_systems(n, k, p, r):
    band = jnp.asarray(random_banded(n, k, d=1.0, seed=7))
    bt = band_to_block_tridiag(band, k, p)
    fac = btf_ref(bt.d, bt.e, bt.f)
    rng = np.random.default_rng(1)
    rhs = jnp.asarray(rng.normal(size=(bt.p, bt.m, bt.k, r)))
    x = bts_ref(fac, rhs)
    dense = np.asarray(block_tridiag_to_dense(bt))
    ni = bt.m * bt.k
    for i in range(p):
        ai = dense[i * ni : (i + 1) * ni, i * ni : (i + 1) * ni]
        xi = np.asarray(x[i]).reshape(ni, r)
        bi = np.asarray(rhs[i]).reshape(ni, r)
        np.testing.assert_allclose(ai @ xi, bi, rtol=1e-3, atol=1e-3)


def test_flip_is_reversal_conjugation():
    band = jnp.asarray(random_banded(48, 4, d=1.0, seed=2))
    bt = band_to_block_tridiag(band, 4, 2)
    d_r, e_r, f_r = flip_block_tridiag(bt.d, bt.e, bt.f)
    # reassemble flipped partition 0 and compare against J A J^T
    import dataclasses

    bt_r = dataclasses.replace(bt, d=d_r, e=e_r, f=f_r)
    a = np.asarray(block_tridiag_to_dense(bt))
    a_r = np.asarray(block_tridiag_to_dense(bt_r))
    ni = bt.m * bt.k
    a0 = a[:ni, :ni]
    np.testing.assert_allclose(a_r[:ni, :ni], a0[::-1, ::-1], atol=1e-6)


def test_ul_factor_solves_like_lu():
    band = jnp.asarray(random_banded(64, 4, d=1.2, seed=3))
    bt = band_to_block_tridiag(band, 4, 2)
    ul = btf_ul_ref(bt.d, bt.e, bt.f)
    rng = np.random.default_rng(4)
    rhs = jnp.asarray(rng.normal(size=(bt.p, bt.m, bt.k, 1)))
    # solving the reversed system with reversed rhs gives reversed solution
    rhs_rev = rhs[:, ::-1, ::-1, :]
    x_rev = bts_ref(ul, rhs_rev)
    x = x_rev[:, ::-1, ::-1, :]
    fac = btf_ref(bt.d, bt.e, bt.f)
    x_lu = bts_ref(fac, rhs)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_lu), rtol=1e-2, atol=1e-3)
