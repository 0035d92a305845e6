"""The solver workload presets (``repro.configs.sap_solver``): each one
builds the engine / service it describes, and each one, shrunk to a CPU
size, solves through the path it configures."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import sap_solver
from repro.core import factor, plan_banded
from repro.core.banded import band_to_dense, oscillatory_banded, random_banded

PRESETS = ["full", "reduced", "exact", "service", "fleet"]


def _preset(name):
    return getattr(sap_solver, name)()


def _system(n, k, d, seed):
    """(band f32, b f32, x from a dense f64 solve) for a matrix of
    dominance ``d`` (oscillatory below 1, so truncation really fails)."""
    gen = oscillatory_banded if d < 1 else random_banded
    band = np.float32(gen(n, k, d=d, seed=seed))
    dense = np.asarray(band_to_dense(jnp.asarray(band)), np.float64)
    b = np.float32(dense @ np.random.default_rng(seed).normal(size=n))
    return band, b, np.linalg.solve(dense, np.float64(b))


def _rel(x, ref):
    return np.linalg.norm(np.asarray(x, np.float64) - ref) / np.linalg.norm(ref)


@pytest.mark.parametrize("preset", PRESETS)
def test_to_engine_carries_batching_knobs(preset):
    cfg = _preset(preset)
    eng = cfg.to_engine(p=8)
    assert eng.max_batch == cfg.max_batch
    assert eng.cache_size == cfg.fac_cache
    assert eng.rounding == cfg.bucket_rounding
    assert eng.cost_accounting == cfg.cost_accounting
    assert eng.opts == cfg.to_sap_options(p=8)


@pytest.mark.parametrize("preset", PRESETS)
def test_to_service_carries_admission_knobs(preset):
    cfg = _preset(preset)
    svc = cfg.to_service(p=8, start=False)
    try:
        assert svc._thread is None  # start=False spawns no drain thread
        assert svc.queue_cap == cfg.queue_cap
        assert svc.default_deadline_s == cfg.deadline_s
        assert (svc.thrash_window, svc.thrash_ratio) == (
            cfg.thrash_window, cfg.thrash_ratio)
        assert (svc.max_batch, svc.rounding) == (cfg.max_batch,
                                                 cfg.bucket_rounding)
        assert svc.engine.cache_size == cfg.fac_cache
        assert svc.engine.opts == cfg.to_sap_options(p=8)
    finally:
        svc.close()


@pytest.mark.parametrize("preset", ["full", "reduced", "exact"])
def test_lifecycle_preset_solves_at_cpu_size(preset):
    """The single-system presets through plan -> factor -> solve."""
    cfg = dataclasses.replace(_preset(preset), n=1024, k=8)
    band, b, x_ref = _system(cfg.n, cfg.k, cfg.d, seed=1)
    fac = factor(plan_banded(jnp.asarray(band), cfg.to_sap_options(p=8)))
    assert fac.variant == ("E" if preset == "exact" else "C")
    res = fac.solve(jnp.asarray(b))
    assert bool(res.converged)
    assert _rel(res.x, x_ref) < 1e-4


def test_service_preset_solves_at_cpu_size():
    """service(): the async front end routes a dominant and a
    non-dominant system to C and E and answers both."""
    cfg = dataclasses.replace(sap_solver.service(), n=512, k=4)
    systems = [_system(cfg.n, cfg.k, d, seed=i)
               for i, d in enumerate((1.2, 0.5))]
    svc = cfg.to_service(p=8, start=False)
    try:
        futs = [svc.submit(band, b) for band, b, _ in systems]
        while svc.pending:
            svc.drain_once()
        outs = [f.result(timeout=0) for f in futs]
    finally:
        svc.close()
    assert [o.variant for o in outs] == ["C", "E"]
    for o, (_, _, x_ref) in zip(outs, systems):
        assert o.converged and not o.misconverged
        assert _rel(o.x, x_ref) < 1e-3


def test_fleet_preset_solves_at_cpu_size():
    """fleet(): one batched engine step answers the whole fleet."""
    cfg = dataclasses.replace(sap_solver.fleet(), n=512, k=4, d=1.2)
    eng = cfg.to_engine(p=8)
    truth = {}
    for i in range(4):
        band, b, x_ref = _system(cfg.n, cfg.k, cfg.d, seed=10 + i)
        truth[eng.submit_system(band, b)] = x_ref
    done = eng.run_until_drained()
    assert eng.stats["steps"] == 1 and len(done) == 4
    for r in done:
        assert r.result.converged and r.result.variant == "C"
        assert _rel(r.result.x, truth[r.rid]) < 1e-3
