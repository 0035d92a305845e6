"""Property-based tests (hypothesis) on system invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import SaPOptions, solve_banded
from repro.core.banded import (
    band_to_dense,
    dense_to_band,
    random_banded,
)
from repro.core import reorder as R
from repro.core.sparse import random_sparse
from repro.kernels import ops

COMMON = dict(deadline=None, max_examples=15, print_blob=True)


@given(
    n=st.integers(8, 60),
    k=st.integers(1, 6),
    seed=st.integers(0, 10_000),
)
@settings(**COMMON)
def test_band_roundtrip_property(n, k, seed):
    k = min(k, n - 1)
    band = jnp.asarray(random_banded(n, k, d=1.0, seed=seed))
    band2 = dense_to_band(band_to_dense(band), k)
    np.testing.assert_allclose(np.asarray(band), np.asarray(band2), atol=1e-6)


@given(
    n=st.integers(40, 150),
    k=st.integers(1, 5),
    p=st.integers(1, 6),
    d=st.floats(1.0, 4.0),
    variant=st.sampled_from(["C", "D"]),
    seed=st.integers(0, 10_000),
)
@settings(**COMMON)
def test_sap_solves_diagonally_dominant_systems(n, k, p, d, variant, seed):
    """Invariant: for d >= 1 the SaP solver converges and matches the dense
    solution to f32 accuracy, for any (n, k, p, variant)."""
    k = min(k, max(1, n // (3 * p)))
    band = jnp.asarray(random_banded(n, k, d=d, seed=seed), jnp.float32)
    dense = np.asarray(band_to_dense(band), dtype=np.float64)
    xstar = np.random.default_rng(seed).normal(size=n)
    b = jnp.asarray(dense @ xstar, jnp.float32)
    sol = solve_banded(band, b, SaPOptions(p=p, variant=variant, tol=1e-6,
                                           maxiter=400))
    assert sol.converged
    err = np.linalg.norm(np.asarray(sol.x) - xstar) / np.linalg.norm(xstar)
    assert err < 5e-3


@given(
    n=st.integers(20, 120),
    seed=st.integers(0, 10_000),
)
@settings(**COMMON)
def test_reorderings_are_permutations(n, seed):
    csr = random_sparse(n, d=1.5, shuffle=True, seed=seed)
    db = R.diagonal_boosting(csr)
    cm = R.cuthill_mckee(R.symmetrize(csr))
    assert sorted(db.tolist()) == list(range(n))
    assert sorted(cm.tolist()) == list(range(n))


@given(
    k=st.sampled_from([2, 4, 8]),
    m=st.integers(2, 6),
    p=st.integers(1, 4),
    dtype=st.sampled_from(["float32", "float64"]),
    seed=st.integers(0, 10_000),
)
@settings(deadline=None, max_examples=10, print_blob=True)
def test_btf_bts_interpret_matches_ref(k, m, p, dtype, seed):
    """Kernel invariant: the Pallas btf/bts kernels in interpret mode agree
    with the pure-jnp references for any (P, M, K) and storage dtype.

    The kernels *compute* in f32 and store in the input dtype (mixed
    precision, paper Sec. 3.1), so agreement is at f32 level even when the
    storage dtype is float64 (and without the x64 flag float64 degrades to
    float32 in both paths anyway).
    """
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dtype)
    d = jnp.asarray(rng.normal(size=(p, m, k, k)), dt) + 4 * jnp.eye(k, dtype=dt)
    e = jnp.asarray(rng.normal(size=(p, m, k, k)) * 0.3, dt)
    f = jnp.asarray(rng.normal(size=(p, m, k, k)) * 0.3, dt)
    b = jnp.asarray(rng.normal(size=(p, m, k, 2)), dt)

    fr = ops.block_tridiag_factor(d, e, f, impl="jnp")
    fp = ops.block_tridiag_factor(d, e, f, impl="interpret")
    tol = dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fr.sinv, np.float64),
                               np.asarray(fp.sinv, np.float64), **tol)
    np.testing.assert_allclose(np.asarray(fr.l, np.float64),
                               np.asarray(fp.l, np.float64), **tol)

    xr = ops.block_tridiag_solve(fr, b, impl="jnp")
    xp = ops.block_tridiag_solve(fr, b, impl="interpret")
    np.testing.assert_allclose(np.asarray(xr, np.float64),
                               np.asarray(xp, np.float64), **tol)


@given(
    m=st.sampled_from([1, 2, 3, 5, 8, 16]),
    k=st.sampled_from([2, 4, 8]),
    dtype=st.sampled_from(["float32", "float64"]),
    seed=st.integers(0, 10_000),
)
@settings(deadline=None, max_examples=10, print_blob=True)
def test_bcr_solve_matches_bts_chain(m, k, dtype, seed):
    """Chain invariant: log-depth cyclic reduction solves any random
    block-tridiagonal chain to the same answer as the sequential
    btf_chain/bts_chain sweep -- including non-power-of-two lengths.

    Like the btf/bts kernels, the factors compute at f32 accuracy (and
    float64 degrades to float32 without the x64 flag anyway), so the
    agreement tolerance is f32-level for both storage dtypes.
    """
    from repro.core.block_lu import btf_chain, bts_chain
    from repro.core.cyclic_reduction import bcr_factor, bcr_solve

    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dtype)
    d = jnp.asarray(rng.normal(size=(m, k, k)), dt) + 4 * jnp.eye(k, dtype=dt)
    e = jnp.asarray(rng.normal(size=(m, k, k)) * 0.3, dt)
    f = jnp.asarray(rng.normal(size=(m, k, k)) * 0.3, dt)
    b = jnp.asarray(rng.normal(size=(m, k, 2)), dt)
    x_seq = bts_chain(btf_chain(d, e, f), b)
    x_bcr = bcr_solve(bcr_factor(d, e, f), b)
    np.testing.assert_allclose(
        np.asarray(x_bcr, np.float64), np.asarray(x_seq, np.float64),
        rtol=5e-4, atol=5e-4,
    )
    x_int = ops.bcr_solve(ops.bcr_factor(d, e, f, impl="interpret"), b,
                          impl="interpret")
    np.testing.assert_allclose(
        np.asarray(x_int, np.float64), np.asarray(x_seq, np.float64),
        rtol=5e-4, atol=5e-4,
    )


@given(
    frac=st.floats(0.0, 0.3),
    seed=st.integers(0, 1000),
)
@settings(**COMMON)
def test_dropoff_budget_invariant(frac, seed):
    csr = random_sparse(80, d=1.0, shuffle=False, seed=seed)
    total = np.abs(csr.data).sum()
    out, k_new = R.drop_off(csr, frac)
    removed = total - np.abs(out.data).sum()
    assert removed <= frac * total + 1e-9
    assert k_new <= max(R.half_bandwidth(csr), 0)
