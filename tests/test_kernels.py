"""Pallas kernel validation: interpret-mode vs pure-jnp oracle, shape/dtype
sweeps (per-kernel allclose against ref.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import bts, ops, ref


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


@pytest.mark.parametrize("p,m,k,r", [
    (2, 3, 4, 1), (1, 6, 8, 8), (3, 2, 16, 4),
    (16, 3, 16, 1),   # the fleet's blocks: all 16 chains in one grid step
    (16, 2, 200, 4),  # the dense blocks: tiles of 8, a proper divisor
    (11, 2, 200, 1),  # no divisor of 11 but 1 fits: one chain a step
    (5, 1, 8, 2),     # M = 1: the first block row is the last
    (4, 3, 16, 16),   # R = K: the spikes' solve
])
def test_bts_matches_ref(p, m, k, r):
    rng = np.random.default_rng(1)
    # A shift that grows with K keeps wide blocks as well conditioned as
    # narrow ones (a K x K normal block has eigenvalues out to sqrt(K)), so
    # the f32 rounding of two summation orders stays within the tolerance.
    d = _rand(rng, (p, m, k, k)) + max(4.0, k / 8) * jnp.eye(k)
    e = _rand(rng, (p, m, k, k)) * 0.3
    f = _rand(rng, (p, m, k, k)) * 0.3
    fac = ref.btf_ref(d, e, f)
    b = _rand(rng, (p, m, k, r))
    xr = ref.bts_ref(fac, b)
    xp = ops.block_tridiag_solve(fac, b, impl="interpret")
    np.testing.assert_allclose(np.asarray(xr), np.asarray(xp), rtol=1e-5,
                               atol=1e-6)


def test_bts_bf16_storage_matches_ref():
    """bf16 blocks and RHS: the kernel computes in f32 and stores bf16, so
    the oracle is the f32 solve of the same stored values."""
    rng = np.random.default_rng(2)
    p, m, k, r = 4, 3, 16, 1
    d = _rand(rng, (p, m, k, k)) + 4 * jnp.eye(k)
    e = _rand(rng, (p, m, k, k)) * 0.3
    f = _rand(rng, (p, m, k, k)) * 0.3
    fac = ref.btf_ref(d, e, f)
    fac16 = type(fac)(*(x.astype(jnp.bfloat16) for x in fac))
    b16 = _rand(rng, (p, m, k, r), jnp.bfloat16)
    xr = ref.bts_ref(type(fac)(*(x.astype(jnp.float32) for x in fac16)),
                     b16.astype(jnp.float32))
    xp = ops.block_tridiag_solve(fac16, b16, impl="interpret")
    assert xp.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(xr), np.asarray(xp, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_bts_under_vmap_matches_ref():
    """The fleet's path: the engine vmaps the solve over systems, so Pallas
    puts the system axis in front of the (P // pt, M) grid."""
    rng = np.random.default_rng(3)
    s, p, m, k, r = 3, 16, 3, 16, 1
    d = _rand(rng, (s, p, m, k, k)) + 4 * jnp.eye(k)
    e = _rand(rng, (s, p, m, k, k)) * 0.3
    f = _rand(rng, (s, p, m, k, k)) * 0.3
    b = _rand(rng, (s, p, m, k, r))
    fac = jax.vmap(ref.btf_ref)(d, e, f)
    xp = jax.vmap(
        lambda fc, bi: ops.block_tridiag_solve(fc, bi, impl="interpret")
    )(fac, b)
    for i in range(s):
        fac_i = ref.btf_ref(d[i], e[i], f[i])
        np.testing.assert_allclose(
            np.asarray(ref.bts_ref(fac_i, b[i])), np.asarray(xp[i]),
            rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("p,k,r,dtype,tile", [
    (16, 16, 1, jnp.float32, 16),    # the fleet
    (16, 200, 1, jnp.float32, 8),    # the dense cell (R 1 under its vmap)
    (16, 200, 4, jnp.float32, 8),
    (16, 200, 200, jnp.float32, 4),  # the dense spikes
    (16, 200, 4, jnp.bfloat16, 8),
    (11, 200, 1, jnp.float32, 1),
    (1, 400, 1, jnp.float32, 1),     # the variant-E chain
])
def test_bts_partition_tile(p, k, r, dtype, tile):
    """The tile is a pure function of the shape: a divisor of P whose
    double-buffered blocks fit the VMEM budget, the largest the rule
    admits."""
    t = bts.partition_tile(p, k, r, dtype)
    assert t == tile and p % t == 0
    blocks = 2 * (2 * bts._vmem_bytes(k, k, dtype)
                  + 2 * bts._vmem_bytes(k, r, dtype))
    assert t * blocks <= bts.VMEM_BUDGET


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
