"""Pallas kernel validation: interpret-mode vs pure-jnp oracle, shape/dtype
sweeps (per-kernel allclose against ref.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# block-tridiag factor / solve
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p,m,k", [(1, 2, 4), (3, 5, 8), (2, 4, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_btf_matches_ref(p, m, k, dtype):
    """The kernel computes in f32 and stores in the input dtype (mixed
    precision, paper Sec 3.1) -- so the oracle is the f32 reference cast
    to the storage dtype."""
    rng = np.random.default_rng(0)
    d = _rand(rng, (p, m, k, k), dtype) + 4 * jnp.eye(k, dtype=dtype)
    e = _rand(rng, (p, m, k, k), dtype) * jnp.asarray(0.3, dtype)
    f = _rand(rng, (p, m, k, k), dtype) * jnp.asarray(0.3, dtype)
    fr = ref.btf_ref(d.astype(jnp.float32), e.astype(jnp.float32),
                     f.astype(jnp.float32))
    fp = ops.block_tridiag_factor(d, e, f, impl="interpret")
    tol = dict(rtol=1e-5, atol=1e-6) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(fr.sinv, np.float32), np.asarray(fp.sinv, np.float32),
        **tol)
    np.testing.assert_allclose(
        np.asarray(fr.l, np.float32), np.asarray(fp.l, np.float32), **tol)


@pytest.mark.parametrize("p,m,k,r", [(2, 3, 4, 1), (1, 6, 8, 8), (3, 2, 16, 4)])
def test_bts_matches_ref(p, m, k, r):
    rng = np.random.default_rng(1)
    d = _rand(rng, (p, m, k, k)) + 4 * jnp.eye(k)
    e = _rand(rng, (p, m, k, k)) * 0.3
    f = _rand(rng, (p, m, k, k)) * 0.3
    fac = ref.btf_ref(d, e, f)
    b = _rand(rng, (p, m, k, r))
    xr = ref.bts_ref(fac, b)
    xp = ops.block_tridiag_solve(fac, b, impl="interpret")
    np.testing.assert_allclose(np.asarray(xr), np.asarray(xp), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("s,p,m,k,r", [(2, 3, 4, 4, 2), (4, 1, 3, 8, 1)])
def test_batched_btf_bts_fold_matches_per_system(s, p, m, k, r):
    """5-dim inputs (a leading system axis) fold the batch into the
    parallel partition grid axis: same math as looping the systems, for
    both the jnp reference and the interpret-mode kernels."""
    rng = np.random.default_rng(5)
    d = _rand(rng, (s, p, m, k, k)) + 4 * jnp.eye(k)
    e = _rand(rng, (s, p, m, k, k)) * 0.3
    f = _rand(rng, (s, p, m, k, k)) * 0.3
    b = _rand(rng, (s, p, m, k, r))
    for impl in ("jnp", "interpret"):
        fac = ops.block_tridiag_factor(d, e, f, impl=impl)
        assert fac.sinv.shape == (s, p, m, k, k)
        x = ops.block_tridiag_solve(fac, b, impl=impl)
        assert x.shape == b.shape
        for i in range(s):
            fac_i = ops.block_tridiag_factor(d[i], e[i], f[i], impl=impl)
            np.testing.assert_allclose(
                np.asarray(fac.sinv[i]), np.asarray(fac_i.sinv),
                rtol=1e-5, atol=1e-6)
            x_i = ops.block_tridiag_solve(fac_i, b[i], impl=impl)
            np.testing.assert_allclose(
                np.asarray(x[i]), np.asarray(x_i), rtol=1e-5, atol=1e-6)


def test_batched_chain_ops_ride_partition_axis():
    """(S, M, K, K) chain batches reuse the partition grid axis."""
    rng = np.random.default_rng(6)
    s, m, k, r = 3, 5, 4, 2
    d = _rand(rng, (s, m, k, k)) + 4 * jnp.eye(k)
    e = _rand(rng, (s, m, k, k)) * 0.3
    f = _rand(rng, (s, m, k, k)) * 0.3
    b = _rand(rng, (s, m, k, r))
    fac = ops.block_tridiag_factor_chain(d, e, f, impl="interpret")
    x = ops.block_tridiag_solve_chain(fac, b, impl="interpret")
    for i in range(s):
        fac_i = ops.block_tridiag_factor_chain(d[i], e[i], f[i],
                                               impl="interpret")
        x_i = ops.block_tridiag_solve_chain(fac_i, b[i], impl="interpret")
        np.testing.assert_allclose(np.asarray(x[i]), np.asarray(x_i),
                                   rtol=1e-5, atol=1e-6)


def test_btf_pivot_boost_in_kernel():
    # a singular diagonal block must not produce NaN thanks to boosting
    d = jnp.zeros((1, 2, 4, 4)).at[:, :, 0, 0].set(1.0)
    e = jnp.zeros_like(d)
    f = jnp.zeros_like(d)
    fac = ops.block_tridiag_factor(d, e, f, boost_eps=1e-6, impl="interpret")
    assert bool(jnp.all(jnp.isfinite(fac.sinv)))


def test_default_impl_is_jnp_on_cpu():
    assert ops.default_impl() == "jnp"
