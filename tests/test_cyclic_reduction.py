"""Block cyclic reduction: log-depth chain solves for the SaP-E reduced
interface system, against the sequential btf/bts chain sweep as oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SaPOptions, factor, plan_banded
from repro.core.banded import band_to_dense, oscillatory_banded
from repro.core.block_lu import btf_chain, bts_chain
from repro.core.cyclic_reduction import (
    bcr_factor,
    bcr_solve,
    pad_chain,
    pcr_factor,
    pcr_n_levels,
    pcr_solve,
    resolve_reduced_solver,
)
from repro.kernels import ops


def _chain(m, k, r=3, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    d = jnp.asarray(rng.normal(size=(m, k, k)), dtype) + 4 * jnp.eye(k, dtype=dtype)
    e = jnp.asarray(rng.normal(size=(m, k, k)) * 0.3, dtype)
    f = jnp.asarray(rng.normal(size=(m, k, k)) * 0.3, dtype)
    b = jnp.asarray(rng.normal(size=(m, k, r)), dtype)
    return d, e, f, b


TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m", [1, 2, 3, 5, 8, 16])
@pytest.mark.parametrize("k", [2, 4, 8])
def test_bcr_matches_chain_sweep(m, k):
    """bcr_factor/bcr_solve == btf_chain/bts_chain for any chain length,
    including non-powers of two (identity padding)."""
    d, e, f, b = _chain(m, k, seed=10 * m + k)
    x_seq = bts_chain(btf_chain(d, e, f), b)
    x_bcr = bcr_solve(bcr_factor(d, e, f), b)
    np.testing.assert_allclose(np.asarray(x_bcr), np.asarray(x_seq), **TOL)


@pytest.mark.parametrize("m", [2, 5, 8])
def test_bcr_interpret_kernels_match_ref(m):
    """The Pallas kernel pair (interpret mode) builds the same factors and
    solution as the pure-jnp reference, through the ops dispatch."""
    k = 4
    d, e, f, b = _chain(m, k, seed=m)
    x_ref = ops.bcr_solve(ops.bcr_factor(d, e, f, impl="jnp"), b, impl="jnp")
    fac_i = ops.bcr_factor(d, e, f, impl="interpret")
    x_int = ops.bcr_solve(fac_i, b, impl="interpret")
    np.testing.assert_allclose(np.asarray(x_int), np.asarray(x_ref), **TOL)
    # factor pytrees are structurally identical across impls
    fac_r = ops.bcr_factor(d, e, f, impl="jnp")
    assert fac_r.m == fac_i.m
    assert len(fac_r.levels) == len(fac_i.levels)
    np.testing.assert_allclose(
        np.asarray(fac_i.root_inv), np.asarray(fac_r.root_inv), **TOL
    )


@pytest.mark.parametrize("m,k,r", [(5, 4, 3), (8, 6, 1), (3, 16, 5), (1, 4, 2)])
def test_bcr_lane_padded_kernels_match_ref(m, k, r):
    """The compiled-path lane padding (small-K blocks embedded into the
    8x128 fp32 tile: identity tail on D, zeros on E/F/RHS) is exact --
    forced on under interpret mode it reproduces the jnp reference, and
    the solution comes back sliced to the original (M, K, R)."""
    from repro.kernels.bcr import bcr_factor_pallas, bcr_solve_pallas

    d, e, f, b = _chain(m, k, r=r, seed=7 * m + k)
    x_ref = bcr_solve(bcr_factor(d, e, f), b)
    fac = bcr_factor_pallas(d, e, f, interpret=True, lane_pad=True)
    kp = fac.root_inv.shape[-1]
    assert kp % 8 == 0 and kp % 128 == 0 and kp >= k  # tile-aligned blocks
    x = bcr_solve_pallas(fac, b, interpret=True)
    assert x.shape == (m, k, r)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_ref), **TOL)


def test_bcr_lane_pad_noop_when_aligned():
    """Blocks already on the (8, 128) tile are left untouched."""
    from repro.kernels.bcr import bcr_factor_pallas

    d, e, f, b = _chain(4, 128, r=2, seed=0)
    fac = bcr_factor_pallas(d, e, f, interpret=True, lane_pad=True)
    assert fac.root_inv.shape == (128, 128)


@pytest.mark.parametrize("m", [1, 3, 7, 8, 13])
def test_pcr_local_shifts_match_chain_sweep(m):
    """The all-active PCR form (the distributed sweep's algorithm) with
    single-device shifts agrees with the sequential chain sweep -- the
    oracle the sharded variant-E path is tested against."""
    k = 4
    d, e, f, b = _chain(m, k, seed=100 + m)
    x_seq = bts_chain(btf_chain(d, e, f), b)
    dp, ep, fp = pad_chain(d, e, f)
    rows = dp.shape[0]
    pf = pcr_factor(dp, ep, fp, pcr_n_levels(m))
    bp = (
        jnp.concatenate([b, jnp.zeros((rows - m,) + b.shape[1:], b.dtype)])
        if rows != m
        else b
    )
    x_pcr = pcr_solve(pf, bp)[:m]
    np.testing.assert_allclose(np.asarray(x_pcr), np.asarray(x_seq), **TOL)


def test_reduced_solver_policy():
    assert resolve_reduced_solver("chain", 1000) == "chain"
    assert resolve_reduced_solver("bcr", 2) == "bcr"
    assert resolve_reduced_solver("auto", 7) == "chain"
    assert resolve_reduced_solver("auto", 8) == "bcr"
    with pytest.raises(ValueError):
        resolve_reduced_solver("nope", 4)


@pytest.mark.parametrize("reduced_solver", ["chain", "bcr"])
def test_variant_e_same_solution_either_reduced_solver(reduced_solver):
    """Variant E is an exact preconditioner solve either way: both reduced
    solvers converge immediately on the hard d=0.5 regime and agree."""
    n, k, p = 512, 6, 16
    band = jnp.asarray(oscillatory_banded(n, k, d=0.5, seed=1), jnp.float32)
    dense = np.asarray(band_to_dense(band))
    xstar = np.random.default_rng(2).normal(size=n)
    b = jnp.asarray(dense @ xstar, jnp.float32)
    fac = factor(
        plan_banded(
            band,
            SaPOptions(p=p, variant="E", tol=1e-5, maxiter=50,
                       reduced_solver=reduced_solver),
        )
    )
    assert fac.pc.reduced_solver == reduced_solver
    res = fac.solve(b)
    assert bool(res.converged)
    assert float(res.iterations) <= 3.0
    err = np.linalg.norm(np.asarray(res.x) - xstar) / np.linalg.norm(xstar)
    assert err < 1e-2


@pytest.mark.parametrize(
    "preset,d,variant,reduced",
    [
        ("full", 1.0, "C", "none"),
        ("reduced", 1.0, "C", "none"),
        ("exact", 0.5, "E", "bcr"),  # 15 interfaces -> auto = bcr
        ("service", 0.5, "E", "bcr"),  # "auto" resolves to E below d = 1
        ("fleet", 1.0, "C", "none"),
    ],
    ids=["full", "reduced", "exact", "service", "fleet"],
)
def test_solver_config_maps_to_sap_options(preset, d, variant, reduced):
    """The config-registry knobs reach the lifecycle API: each workload
    preset's options carry its fields, and a matrix of dominance ``d``
    factors as the variant (and reduced-chain solver) the preset picks."""
    from repro.configs import sap_solver

    cfg = getattr(sap_solver, preset)()
    opts = cfg.to_sap_options(p=16)
    assert opts.p == 16
    assert (opts.variant, opts.reduced_solver) == (cfg.variant,
                                                   cfg.reduced_solver)
    assert (opts.tol, opts.maxiter, opts.precond_dtype) == (
        cfg.tol, cfg.maxiter, cfg.precond_dtype)
    band = jnp.asarray(oscillatory_banded(512, 6, d=d, seed=3), jnp.float32)
    fac = factor(plan_banded(band, opts))
    assert fac.variant == variant
    assert fac.pc.reduced_solver == reduced


def test_reduced_solver_choice_in_info():
    """The resolved choice rides the preconditioner pytree and the
    one-shot info dict."""
    from repro.core import solve_banded

    n, k = 512, 6
    band = jnp.asarray(oscillatory_banded(n, k, d=0.5, seed=1), jnp.float32)
    dense = np.asarray(band_to_dense(band))
    b = jnp.asarray(dense @ np.ones(n), jnp.float32)
    sol = solve_banded(band, b, SaPOptions(p=16, variant="E", tol=1e-5))
    assert sol.info["reduced_solver"] == "bcr"  # P-1 = 15 >= 8 -> bcr
    sol = solve_banded(band, b, SaPOptions(p=4, variant="E", tol=1e-5))
    assert sol.info["reduced_solver"] == "chain"
    sol = solve_banded(band, b, SaPOptions(p=4, variant="D", tol=1e-5))
    assert sol.info["reduced_solver"] == "none"
