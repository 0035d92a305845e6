"""Closed loop: one client that waits for each answer before the next step.

A step takes the next of ``matrices`` device-resident seeded systems in
turn and solves it for R fresh right-hand sides (``rhs_pool`` sets made at
set-up, used in turn) through the solver's lifecycle
``plan_banded -> factor -> solve_many``.  The check takes
``check_steps`` steps drawn from the seed.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import generate, reference
from chipbench.tracing import annotate

CHECK = 9  # sub-stream of the seed that draws the sample of answers checked


def _opts(cfg: dict):
    from repro.core import SaPOptions

    return SaPOptions(p=cfg["p"], variant=cfg["variant"], tol=cfg["tol"],
                      maxiter=cfg["maxiter"], solver=cfg["solver"],
                      precond_dtype=cfg["dtype"])


@jax.jit
def _split_pool(x):
    return tuple(x[i] for i in range(x.shape[0]))


def _pool(key, count: int, make) -> tuple:
    """``count`` separate device arrays from one generating call."""
    return _split_pool(make(key, count))


def run(cfg: dict, traffic: dict, seed: int, seconds: float, window) -> dict:
    """Set up, run the window, check a sample; the run's record."""
    from repro.core import factor, plan_banded

    t_setup = time.perf_counter()
    n, k, r = cfg["n"], cfg["k"], cfg["nrhs"]
    count, pool = traffic["matrices"], traffic["rhs_pool"]
    opts = _opts(cfg)
    with annotate("generate"):
        bands = _pool(generate.stream(seed, generate.BANDS), count,
                      lambda key, c: generate.bands(key, c, n, k, cfg["d"]))
        rhs = _pool(generate.stream(seed, generate.RHS), pool,
                    lambda key, c: generate.normal(key, (c, n, r)))
        warm_b = generate.normal(generate.stream(seed, generate.WARM), (n, r))
        jax.block_until_ready((bands, rhs, warm_b))

    with annotate("warmup"):
        for band in bands[: traffic["warm_steps"]]:
            jax.block_until_ready(factor(plan_banded(band, opts)).solve_many(warm_b))
    setup_s = time.perf_counter() - t_setup

    steps = []
    with window as win:
        t_end = time.perf_counter() + seconds
        s = 0
        while time.perf_counter() < t_end:
            i, j = s % count, s % pool
            t0 = time.perf_counter()
            with annotate("plan"):
                pl = plan_banded(bands[i], opts)
            with annotate("factor"):
                fac = jax.block_until_ready(factor(pl))
            t1 = time.perf_counter()
            with annotate("solve"):
                res = jax.block_until_ready(fac.solve_many(rhs[j]))
            t2 = time.perf_counter()
            steps.append({"matrix": i, "rhs": j, "t0": t0, "t_factor": t1 - t0,
                          "t_solve": t2 - t1, "t_end": t2,
                          "x": res.x, "iterations": res.iterations})
            s += 1
        fac = pl = res = None
    window_s = steps[-1]["t_end"] - win.t0

    for st in steps:
        st["iterations"] = float(np.mean(np.asarray(st.pop("iterations"))))
    rec = {"setup_s": setup_s, "window_s": window_s, "steps": steps,
           "attempted": len(steps) * r}
    win.close_device()

    # the check: a sample of steps drawn from the seed, in float64 on the host
    rng = np.random.default_rng([seed, CHECK])
    sample = sorted(rng.choice(len(steps), min(traffic["check_steps"], len(steps)),
                               replace=False).tolist())
    limit = cfg["check"]["max_residual"]
    with annotate("check"):
        res = []
        for i in sorted({steps[s]["matrix"] for s in sample}):
            mine = [s for s in sample if steps[s]["matrix"] == i]
            x = np.concatenate([np.asarray(steps[s]["x"]) for s in mine], axis=1)
            b = np.concatenate([np.asarray(rhs[steps[s]["rhs"]]) for s in mine], axis=1)
            res.append(reference.residual(np.asarray(bands[i]), x, b))
    res = np.nan_to_num(np.concatenate(res), nan=np.inf)
    for st in steps:
        del st["x"]
    failed = int(np.sum(~(res <= limit)))
    rec.update(failed=failed, correct=failed == 0,
               compared={"max_residual": {"value": float(res.max()), "limit": limit}})
    return rec


