"""Bring-up smoke run of the SaP solver on a TPU, through its public entry points.

    python chip_smoke.py               # one chip: phases 1-4 below
    python chip_smoke.py --four-chips  # 2x2 mesh: the sharded solver only

Phases run in one process, in order; the first failure raises and the
process exits non-zero.  There is no CPU fallback: without a TPU the device
phase fails.

1. device   -- JAX's first device must be a TPU.  Prints its kind, the
               device count, the JAX version, the kernel implementation
               (must be "pallas") and the compile-cache directory.
2. dense C  -- the paper's dense banded system (``configs.sap_solver.full``:
               N = 200,000, K = 200, d = 1, P = 16, f32) through
               ``plan_banded`` -> ``factor`` -> ``solve_many`` on 4 RHS.
3. dense E  -- the non-dominant system (``exact``: same N and K,
               ``oscillatory_banded`` at d = 0.5), variant E with the BCR
               reduced-chain solver and iterative refinement as the outer
               loop (see ``main``).
4. served   -- ``service()`` (N = 16,384, K = 16, variant "auto") through
               ``SolverConfig.to_service``: 16 seeded requests over 4
               matrices, half with d >= 1 and half with d = 0.5.

Every answer is checked on the host in f64: ||b - A x|| / ||b|| <= 10 tol.
The dense phases also report the relative error against
``scipy.linalg.solve_banded`` on the same system.  Wall times printed here
are smoke timings of one run (cold = first call, compile included), not
benchmark numbers.

``--four-chips`` runs ``build_dist_sap`` + ``solve_step_fn`` on a 2x2 mesh
at ``dense_1m`` (N = 1,048,576, K = 500): variant C at d = 1 and
``variant="auto"`` (-> E) at d = 0.5, both ``random_banded``.  For comparison it solves
``dense_200k`` both sharded and with the single-chip lifecycle; the two
solutions must agree.

The last line of stdout is the JSON contract line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The phase functions take their sizes, so tests run them small on CPU with
``REPRO_KERNEL_IMPL=interpret``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

_SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(_SRC))

import repro  # noqa: E402

if Path(repro.__file__).resolve().parent.parent != _SRC:
    raise ImportError(
        f"imported repro from {repro.__file__}, not the one next to this "
        f"script ({_SRC}); run chip_smoke.py from a checkout of the repo"
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import scipy.linalg  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import sap_solver  # noqa: E402
from repro.core import SaPOptions, factor, plan_banded  # noqa: E402
from repro.core.banded import oscillatory_banded, random_banded  # noqa: E402
from repro.kernels.ops import default_impl  # noqa: E402
from repro.serve.solver_engine import SolveOutcome  # noqa: E402

# f32 stalls near 1e-7 relative residual, short of the configs' 1e-8.
F32_TOL = 1e-6


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _emit(name: str, report: dict) -> None:
    print(f"{name}: {json.dumps(report)}", flush=True)


# ---------------------------------------------------------------------------
# Host f64 references
# ---------------------------------------------------------------------------


def host_residual(band: np.ndarray, x: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Per-column ||b - A x|| / ||b|| in f64, A in (N, 2K+1) band storage."""
    band = np.asarray(band, np.float64)
    x = np.asarray(x, np.float64).reshape(band.shape[0], -1)
    b = np.asarray(b, np.float64).reshape(band.shape[0], -1)
    n, w = band.shape
    k = (w - 1) // 2
    ax = np.zeros_like(x)
    for j in range(w):  # ax[r] += band[r, j] * x[r + j - k]
        s = j - k
        lo, hi = max(0, -s), min(n, n - s)
        ax[lo:hi] += band[lo:hi, j, None] * x[lo + s : hi + s]
    return np.linalg.norm(b - ax, axis=0) / np.linalg.norm(b, axis=0)


def scipy_solve(band: np.ndarray, b: np.ndarray) -> np.ndarray:
    """f64 banded LU with partial pivoting (LAPACK gbsv) on the same system."""
    n, w = band.shape
    k = (w - 1) // 2
    ab = np.zeros((w, n))  # LAPACK layout: ab[k + i - c, c] = A[i, c]
    for j in range(w):
        s = j - k  # column c = i + s
        ab[k - s, max(0, s) : n + min(0, s)] = band[max(0, -s) : n - max(0, s), j]
    return scipy.linalg.solve_banded((k, k), ab, b)


def _timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def device_phase() -> dict:
    """Require a TPU; return the contract's device record."""
    devices = jax.devices()
    d0 = devices[0]
    _check(
        d0.platform == "tpu",
        f"no TPU: JAX's first device is {d0.platform!r} ({d0.device_kind}); "
        "this smoke run has no CPU fallback",
    )
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}


def dense_phase(name: str, cfg, *, p: int, nrhs: int, tol: float, seed: int,
                generator=random_banded, solver: str = "auto") -> dict:
    """plan_banded -> factor -> solve_many on one seeded banded system.

    Prints its report before checking it, so a failed check still shows
    every number."""
    impl = default_impl()
    band = generator(cfg.n, cfg.k, cfg.d, seed=seed)
    b = np.random.default_rng(seed + 1).standard_normal((cfg.n, nrhs))
    opts = dataclasses.replace(cfg.to_sap_options(p), tol=tol, solver=solver)
    pl = plan_banded(jnp.asarray(band, jnp.float32), opts)
    fac, factor_cold = _timed(lambda: factor(pl))
    fac, factor_warm = _timed(lambda: factor(pl))
    bj = jnp.asarray(b, jnp.float32)
    res, solve_cold = _timed(lambda: fac.solve_many(bj))
    res, solve_warm = _timed(lambda: fac.solve_many(bj))

    x = np.asarray(res.x, np.float64)
    resid = host_residual(band, x, b)
    x_ref = scipy_solve(band, b)
    rel_err = np.linalg.norm(x - x_ref, axis=0) / np.linalg.norm(x_ref, axis=0)
    report = {
        "n": cfg.n, "k": cfg.k, "d": cfg.d, "p": p, "nrhs": nrhs, "tol": tol,
        "variant": fac.variant, "solver": fac.solver, "impl": fac.pc.impl,
        "fused": fac.pc.fused,
        "reduced_solver": fac.pc.reduced_solver,
        "iterations": np.asarray(res.iterations).tolist(),
        "converged": np.asarray(res.converged).tolist(),
        "resnorm": np.asarray(res.resnorm).tolist(),
        "true_resnorm": np.asarray(res.true_resnorm).tolist(),
        "host_f64_residual": resid.tolist(),
        "rel_err_vs_scipy": rel_err.tolist(),
        "smoke_seconds": {
            "factor_cold": factor_cold, "factor_warm": factor_warm,
            "solve_cold": solve_cold, "solve_warm": solve_warm,
        },
    }
    _emit(name, report)
    expect = cfg.variant if cfg.variant != "auto" else fac.variant
    _check(fac.variant == expect, f"factored variant {fac.variant}, want {expect}")
    _check(fac.pc.impl == impl, f"preconditioner ran impl {fac.pc.impl!r}, want {impl!r}")
    _check(fac.pc.fused == (impl == "pallas"),
           f"fused={fac.pc.fused} under impl {impl!r}")
    _check(bool(np.all(report["converged"])), f"not converged: {report}")
    _check(bool(np.all(resid <= 10 * tol)),
           f"host f64 residual {resid.tolist()} > 10 * tol = {10 * tol}")
    return report


def served_phase(cfg, *, p: int, n_matrices: int, per_matrix: int, seed: int,
                 timeout_s: float) -> dict:
    """Seeded requests through the async service; every future must
    resolve to a converged SolveOutcome that passes the f64 check."""
    # d = 1.2, not 1.0: the host dominance estimate of a d = 1 band rounds
    # to just below 1 and would route it to the non-dominant class.
    systems = []
    for i in range(n_matrices):
        d = (1.2, 0.5)[i % 2]
        band = random_banded(cfg.n, cfg.k, d, seed=seed + i)
        rng = np.random.default_rng(seed + 100 + i)
        systems += [(band, d, rng.standard_normal(cfg.n)) for _ in range(per_matrix)]

    svc = cfg.to_service(p)
    try:
        futures = [svc.submit(band, b) for band, _, b in systems]
        outcomes = [f.outcome(timeout_s) for f in futures]
        snap = svc.snapshot()
    finally:
        svc.close()

    solved = [isinstance(out, SolveOutcome) for out in outcomes]
    resid = [float(host_residual(band, out.x, b)[0]) if ok else float("nan")
             for (band, _, b), out, ok in zip(systems, outcomes, solved)]
    variants = [out.variant if ok else None for out, ok in zip(outcomes, solved)]
    report = {
        "n": cfg.n, "k": cfg.k, "p": p, "requests": len(systems),
        "matrices": n_matrices, "tol": cfg.tol, "variants": variants,
        "host_f64_residual": resid,
        "cache_hit_rate": snap["derived"]["cache_hit_rate"],
        "recompiles_total": snap["engine"]["recompiles_total"],
    }
    _emit("served", report)
    for (_, d, _), out, r in zip(systems, outcomes, resid):
        _check(isinstance(out, SolveOutcome), f"request with d={d} ended as {out!r}")
        _check(out.converged, f"request with d={d} did not converge: {out}")
        _check(r <= 10 * cfg.tol, f"host f64 residual {r} > 10 * tol (d={d})")
    _check(set(variants) == {"C", "E"}, f"served variants {variants}, want C and E")
    return report


def four_chip_phase(big, small, *, p_per_device: int, tol: float, seed: int,
                    agree: float) -> dict:
    """Sharded SaP on a 2x2 mesh: dense C and auto (-> E) at ``big``, and
    ``small`` solved sharded against the single-chip lifecycle."""
    from repro.core.distributed import build_dist_sap, solve_step_fn
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh((2, 2), ("data", "model"))

    def dist_solve(shape, d, variant):
        band = random_banded(shape.n, shape.k, d, seed=seed)
        b = np.random.default_rng(seed + 1).standard_normal(shape.n)
        dsap = build_dist_sap(mesh, shape.n, shape.k, variant=variant,
                              p_per_device=p_per_device, band=band)
        band_p, b_p, parts = dsap.shard_band(band, b)
        step = jax.jit(solve_step_fn(dsap, tol=tol, maxiter=200))
        args = (band_p, b_p, *(parts[n] for n in ("d", "e", "f", "b_next", "c_prev")))
        with mesh:  # timed once, compile included
            res, cold = _timed(lambda: step(*args))
        x = np.asarray(res.x, np.float64)[: shape.n]
        r = float(host_residual(band, x, b)[0])
        report = {
            "n": shape.n, "k": shape.k, "d": d, "variant": dsap.variant,
            "iterations": float(res.iterations), "converged": bool(res.converged),
            "resnorm": float(res.resnorm), "true_resnorm": float(res.true_resnorm),
            "host_f64_residual": r, "smoke_seconds": {"cold": cold},
        }
        _emit(f"four_chips_{shape.name}_{variant}", report)
        _check(report["converged"], f"{shape.name} d={d} {variant}: not converged")
        _check(r <= 10 * tol, f"{shape.name} d={d} {variant}: host f64 residual {r}")
        return report, band, b, x

    out = {"p": 4 * p_per_device}
    out["C"] = dist_solve(big, 1.0, "C")[0]
    out["auto"] = dist_solve(big, 0.5, "auto")[0]
    _check(out["auto"]["variant"] == "E", f"auto resolved to {out['auto']['variant']}")

    out["compare"], band, b, x_dist = dist_solve(small, 1.0, "C")
    opts = SaPOptions(p=4 * p_per_device, variant="C", tol=tol, maxiter=200)
    res = factor(plan_banded(jnp.asarray(band, jnp.float32), opts)).solve(
        jnp.asarray(b, jnp.float32))
    x_one = np.asarray(res.x, np.float64)
    diff = float(np.linalg.norm(x_dist - x_one) / np.linalg.norm(x_one))
    out["compare"]["rel_diff_vs_single_chip"] = diff
    _emit("four_chips", out)
    _check(diff <= agree, f"sharded vs single-chip solutions differ by {diff}")
    return out


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    """Run the phases; the last stdout line is the contract line."""
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the sharded solver on a 2x2 mesh")
    args = parser.parse_args(argv)

    cache_dir = enable_compile_cache()
    device = device_phase()
    impl = default_impl()
    print(f"device_kind: {device['kind']}", flush=True)
    print(f"device_count: {device['count']}", flush=True)
    print(f"jax_version: {jax.__version__}", flush=True)
    print(f"kernel_impl: {impl}", flush=True)
    print(f"compile_cache_dir: {cache_dir}", flush=True)
    _check(impl == "pallas", f"kernel impl {impl!r} on a TPU; want 'pallas'")

    if args.four_chips:
        _check(device["count"] == 4, f"--four-chips needs 4 chips, found {device['count']}")
        shapes = sap_solver.SOLVER_SHAPES
        four_chip_phase(shapes["dense_1m"], shapes["dense_200k"], p_per_device=4,
                        tol=F32_TOL, seed=0, agree=1e-5)
    else:
        dense_phase("dense_C", sap_solver.full(), p=16, nrhs=4, tol=F32_TOL, seed=0)
        # BiCGStab(2) stops on the left-preconditioned residual, and with
        # the f32 variant-E factors of the oscillatory band that misconverges
        # (true residual 4e-6 .. 1.5e-3 on CPU up to N = 60,000, K = 120).
        # Refinement controls the true residual: 2-3 sweeps to below tol.
        exact = dense_phase("dense_E", sap_solver.exact(), p=16, nrhs=4, tol=F32_TOL,
                            seed=0, generator=oscillatory_banded, solver="refine")
        _check(exact["reduced_solver"] == "bcr",
               f"variant E reduced solver {exact['reduced_solver']!r}, want 'bcr'")
        served_phase(sap_solver.service(), p=16, n_matrices=4, per_matrix=4,
                     seed=0, timeout_s=900.0)

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
