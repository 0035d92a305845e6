"""SolverEngine: bucketed batched serving with an LRU factorization cache."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SaPOptions, batched
from repro.core.banded import (
    band_matvec,
    band_to_dense,
    oscillatory_banded,
    random_banded,
)
from repro.core.krylov import bicgstab2_applies
from repro.serve import SolveRequest, SolverEngine, matrix_fingerprint


def _mat(n, k, seed, d=1.1):
    return np.float32(random_banded(n, k, d=d, seed=seed))


def _rhs_for(band, seed):
    n = band.shape[0]
    x = np.random.default_rng(seed).normal(size=n)
    b = np.asarray(band_matvec(jnp.asarray(band), jnp.asarray(x, jnp.float32)))
    return x, b


def _engine(**kw):
    kw.setdefault("max_batch", 8)
    return SolverEngine(SaPOptions(p=4, variant="C", tol=1e-6, maxiter=300), **kw)


def test_fingerprint_is_content_keyed():
    a = _mat(64, 3, seed=0)
    assert matrix_fingerprint(a) == matrix_fingerprint(a.copy())
    b = a.copy()
    b[10, 1] += 1e-3
    assert matrix_fingerprint(a) != matrix_fingerprint(b)
    # dtype and shape are part of the key
    assert matrix_fingerprint(a) != matrix_fingerprint(a.astype(np.float64))


def test_engine_solves_heterogeneous_fleet():
    eng = _engine()
    mats = [_mat(150 + 37 * i, 3 + i % 2, seed=i) for i in range(5)]
    truth = {}
    for i, band in enumerate(mats):
        x, b = _rhs_for(band, seed=50 + i)
        truth[eng.submit_system(band, b)] = x
    done = eng.run_until_drained()
    assert len(done) == 5 and eng.queue == type(eng.queue)()
    for r in done:
        assert r.result.converged
        x = truth[r.rid]
        assert r.result.x.shape == x.shape  # un-padded to original N
        err = np.linalg.norm(r.result.x - x) / np.linalg.norm(x)
        assert err < 1e-3


@pytest.mark.parametrize("variant,d", [("D", 1.1), ("E", 0.5)])
def test_engine_solves_fleet_under_variant(variant, d):
    """The batched engine under the decoupled (D) and exact reduced (E)
    variants, the latter on non-dominant matrices with non-decaying
    spikes, against dense f64 solves."""
    eng = SolverEngine(
        SaPOptions(p=4, variant=variant, tol=1e-6, maxiter=300), max_batch=8
    )
    truth = {}
    for i in range(3):
        band = np.float32(oscillatory_banded(192, 3, d=d, seed=20 + i))
        dense = np.asarray(band_to_dense(jnp.asarray(band)), np.float64)
        b = np.float32(dense @ np.random.default_rng(i).normal(size=192))
        truth[eng.submit_system(band, b)] = np.linalg.solve(dense, b)
    done = eng.run_until_drained()
    assert len(done) == 3 and eng.stats["steps"] == 1
    for r in done:
        assert r.result.variant == variant
        assert r.result.converged and not r.result.misconverged
        x = truth[r.rid]
        assert np.linalg.norm(r.result.x - x) / np.linalg.norm(x) < 1e-3


def test_engine_factor_runs_once_for_repeated_fingerprints(monkeypatch):
    """The cache-hit call-count contract: re-submitting the same matrix
    across steps (implicit time stepping) factors it exactly once."""
    calls = {"batches": 0, "systems": 0}
    real = batched.batch_factor

    def counting(bpl):
        calls["batches"] += 1
        calls["systems"] += bpl.s
        return real(bpl)

    monkeypatch.setattr(batched, "batch_factor", counting)
    eng = _engine()
    band = _mat(200, 4, seed=7)
    for step in range(4):  # 4 "time steps", fresh RHS each, same matrix
        x, b = _rhs_for(band, seed=step)
        eng.submit_system(band, b)
        done = eng.step()
        assert len(done) == 1 and done[0].result.converged
        assert done[0].result.cache_hit == (step > 0)
    assert calls == {"batches": 1, "systems": 1}
    assert eng.stats["cache_hits"] == 3
    assert eng.stats["cache_misses"] == 1
    assert eng.stats["factored_systems"] == 1
    assert eng.cache_hit_rate == 0.75


def test_engine_duplicate_fingerprints_in_one_batch(monkeypatch):
    """Duplicates inside a single step factor once; later copies are hits."""
    calls = {"systems": 0}
    real = batched.batch_factor

    def counting(bpl):
        calls["systems"] += bpl.s
        return real(bpl)

    monkeypatch.setattr(batched, "batch_factor", counting)
    eng = _engine()
    band = _mat(200, 4, seed=1)
    for i in range(4):  # same Jacobian, 4 outstanding RHS requests
        eng.submit_system(band, _rhs_for(band, seed=i)[1])
    done = eng.step()
    assert len(done) == 4
    assert calls["systems"] == 1
    assert eng.stats["cache_hits"] == 3 and eng.stats["cache_misses"] == 1


def test_engine_lru_eviction_stays_correct():
    eng = _engine(cache_size=1)
    m1, m2 = _mat(200, 4, seed=1), _mat(200, 4, seed=2)
    for rep in range(2):  # alternate matrices: each round evicts the other
        for seed, band in ((rep, m1), (10 + rep, m2)):
            x, b = _rhs_for(band, seed=seed)
            eng.submit_system(band, b)
            (done,) = eng.step()
            assert done.result.converged
            err = np.linalg.norm(done.result.x - x) / np.linalg.norm(x)
            assert err < 1e-3
    assert eng.stats["evictions"] >= 2
    assert eng.cached_factorizations == 1


def test_engine_batch_larger_than_cache_survives_midstep_eviction():
    """Regression: cache_size below the distinct matrices of one step
    must not lose the factorizations the in-flight batch still needs."""
    eng = _engine(max_batch=8, cache_size=1)
    truth = {}
    for i in range(3):  # 3 distinct same-bucket matrices in ONE step
        band = _mat(200, 4, seed=20 + i)
        x, b = _rhs_for(band, seed=i)
        truth[eng.submit_system(band, b)] = x
    done = eng.step()
    assert len(done) == 3
    for r in done:
        assert r.result.converged
        err = np.linalg.norm(r.result.x - truth[r.rid])
        assert err / np.linalg.norm(truth[r.rid]) < 1e-3
    assert eng.cached_factorizations == 1  # LRU still capped
    assert eng.stats["evictions"] == 2


def test_engine_batches_one_bucket_per_step():
    """max_batch caps a step; different buckets never share a batch."""
    eng = _engine(max_batch=2)
    small = [_mat(100, 3, seed=i) for i in range(3)]  # bucket (256, 4, 4)
    big = _mat(600, 3, seed=9)  # bucket (1024, 4, 4)
    for band in [*small, big]:
        eng.submit_system(band, _rhs_for(band, seed=0)[1])
    done1 = eng.step()  # largest bucket first, capped at 2
    assert len(done1) == 2
    assert {r.result.bucket for r in done1} == {(256, 4, 4)}
    done_rest = eng.run_until_drained()
    assert len(done_rest) == 2
    assert eng.stats["solved"] == 4 and eng.stats["steps"] == 3


def test_engine_sticky_auto_variant():
    """variant='auto' pins itself after the first factored batch so cached
    and fresh factorizations always stack into one pytree structure."""
    eng = SolverEngine(
        SaPOptions(p=4, variant="auto", tol=1e-5, maxiter=200), max_batch=4
    )
    band = _mat(200, 4, seed=3, d=1.5)  # dominant -> resolves to C
    x, b = _rhs_for(band, seed=0)
    eng.submit_system(band, b)
    (done,) = eng.step()
    assert done.result.converged
    assert eng.opts.variant == "C"
    # a second, different matrix reuses the pinned variant
    band2 = _mat(230, 4, seed=4, d=1.5)
    x2, b2 = _rhs_for(band2, seed=1)
    eng.submit_system(band2, b2)
    (done2,) = eng.step()
    assert done2.result.converged


def test_engine_step_on_empty_queue_is_noop():
    eng = _engine()
    assert eng.step() == []
    assert eng.stats["steps"] == 0


def test_run_until_drained_warns_on_leftover_work():
    """Regression: hitting max_steps with work still queued used to
    return silently -- now it warns (or raises) with the queue depth."""
    eng = _engine(max_batch=1)
    band = _mat(100, 3, seed=0)
    for i in range(3):
        eng.submit_system(band, _rhs_for(band, seed=i)[1])
    with pytest.warns(RuntimeWarning, match=r"2 request\(s\) still queued"):
        done = eng.run_until_drained(max_steps=1)
    assert len(done) == 1 and eng.pending == 2
    with pytest.raises(RuntimeError, match=r"1 request\(s\) still queued"):
        eng.run_until_drained(max_steps=1, on_leftover="raise")
    assert eng.run_until_drained() and eng.pending == 0  # no leftover: quiet


def test_solve_prepared_accepts_preformed_bucket():
    """An external scheduler can hand the engine a batch + bucket + per-
    call options without touching the internal queue."""
    from repro.serve.solver_engine import SolveRequest as SR

    eng = _engine()
    band = _mat(150, 3, seed=0)
    x, b = _rhs_for(band, seed=0)
    reqs = [SR(rid=0, band=band, b=b)]
    bucket = batched.bucket_shape(150, 3, 4, "pow2")
    opts = SaPOptions(p=4, variant="C", tol=1e-6, maxiter=300)
    done = eng.solve_prepared(reqs, bucket, opts=opts)
    assert len(done) == 1 and done[0].result.converged
    assert done[0].result.variant == "C"
    assert done[0].result.bucket == bucket
    err = np.linalg.norm(done[0].result.x - x) / np.linalg.norm(x)
    assert err < 1e-3
    assert eng.pending == 0 and eng.stats["solved"] == 1
    assert eng.solve_prepared([], bucket) == []


def test_cache_keys_include_options_signature():
    """The same matrix under different variants must occupy distinct
    cache entries (different pytree structures cannot stack)."""
    from repro.serve.solver_engine import SolveRequest as SR

    eng = _engine(cache_size=8)
    band = _mat(150, 3, seed=0)
    _, b = _rhs_for(band, seed=0)
    bucket = batched.bucket_shape(150, 3, 4, "pow2")
    for variant in ("C", "E"):
        opts = SaPOptions(p=4, variant=variant, tol=1e-6, maxiter=300)
        (done,) = eng.solve_prepared([SR(rid=0, band=band, b=b)], bucket,
                                     opts=opts)
        assert done.result.converged and done.result.variant == variant
    assert eng.cached_factorizations == 2
    assert eng.stats["cache_misses"] == 2  # no cross-variant false hit


def test_engine_concurrent_submit_and_step_thread_safe():
    """Client threads submitting while another thread steps: no request
    is lost, every result converges, counters stay consistent."""
    import threading

    eng = _engine(max_batch=4)
    mats = [_mat(100 + 10 * (i % 3), 3, seed=i % 4) for i in range(12)]
    # pre-warm the jit caches so the stepping loop below is fast
    x0, b0 = _rhs_for(mats[0], seed=0)
    eng.submit_system(mats[0], b0)
    eng.run_until_drained()

    def client(tid):
        rng = np.random.default_rng(tid)
        for i in range(4):
            band = mats[(tid * 4 + i) % len(mats)]
            eng.submit_system(band, rng.normal(size=band.shape[0]))

    threads = [threading.Thread(target=client, args=(t,)) for t in range(3)]
    for t in threads:
        t.start()
    done = []
    deadline = time.monotonic() + 120
    while len(done) < 12 and time.monotonic() < deadline:
        done.extend(eng.step())
    for t in threads:
        t.join(timeout=60)
    assert len(done) == 12
    assert all(r.result.converged for r in done)
    assert eng.stats["solved"] == 13 and eng.pending == 0


def test_submit_precomputed_fingerprint_respected():
    eng = _engine()
    band = _mat(100, 3, seed=0)
    _, b = _rhs_for(band, seed=0)
    req = SolveRequest(rid=99, band=band, b=b, fingerprint="custom-fp")
    eng.submit(req)
    assert req.fingerprint == "custom-fp"
    (done,) = eng.step()
    assert done.rid == 99 and done.result.converged


class _NumpySpy:
    """numpy, recording each conversion of a watched array to numpy."""

    CONVERT = ("asarray", "array", "ascontiguousarray")

    def __init__(self, watched):
        self.watched, self.seen = watched, []

    def __getattr__(self, name):
        fn = getattr(np, name)
        if name not in self.CONVERT:
            return fn

        def spy(a, *args, **kw):
            if any(a is w for w in self.watched):
                self.seen.append(name)
            return fn(a, *args, **kw)

        return spy


def test_keyed_request_never_hashes_nor_reads_its_band(monkeypatch):
    """The time-stepping contract: a request whose caller names its
    Jacobian is never hashed and its device band never becomes numpy."""
    from repro.serve import solver_engine

    band = jnp.asarray(_mat(200, 4, seed=5))
    _, b = _rhs_for(np.asarray(band), seed=0)
    spy = _NumpySpy([band])
    hashed = []
    real_fp = solver_engine.matrix_fingerprint
    monkeypatch.setattr(solver_engine, "np", spy)
    monkeypatch.setattr(batched, "np", spy)
    monkeypatch.setattr(solver_engine, "matrix_fingerprint",
                        lambda a: hashed.append(a) or real_fp(a))
    eng = _engine()
    for step in range(2):  # a miss, then a hit
        eng.submit(SolveRequest(rid=step, band=band, b=jnp.asarray(b),
                                fingerprint="jacobian.0"))
        (done,) = eng.step()
        assert done.result.converged and done.result.cache_hit == (step > 0)
    assert hashed == [] and spy.seen == []
    # the spies see the default path: an unkeyed request is hashed on the host
    eng.submit_system(band, b)
    eng.step()
    assert len(hashed) == 1 and spy.seen


# A small seeded fleet under the time-stepping schedule: 8 systems, 2 of
# them refreshed a step (systems 2(s mod 4), 2(s mod 4) + 1), 6 steps after
# the step that factors every system's first Jacobian.
FLEET_N, FLEET_K, SYSTEMS, REFRESH, STEPS = 256, 4, 8, 2, 6
COUNTERS = ("cache_hits", "cache_misses", "factored_systems", "escalations",
            "krylov_iters_total", "krylov_lane_max_total", "krylov_applies_total",
            "steps")


@pytest.fixture(scope="module")
def fleet():
    eng = SolverEngine(
        SaPOptions(p=4, variant="C", tol=1e-6, maxiter=300, solver="bicgstab2"),
        max_batch=SYSTEMS, cache_size=32,
    )
    version = [0] * SYSTEMS
    rng = np.random.default_rng(0)
    steps = []
    for s in range(-1, STEPS):
        if s >= 0:
            first = REFRESH * (s % (SYSTEMS // REFRESH))
            for i in range(first, first + REFRESH):
                version[i] += 1
        bands = [_mat(FLEET_N, FLEET_K, seed=1000 * i + version[i], d=1.2)
                 for i in range(SYSTEMS)]
        bs = [rng.normal(size=FLEET_N).astype(np.float32) for _ in range(SYSTEMS)]
        before = {c: eng.stats[c] for c in COUNTERS}
        for i in range(SYSTEMS):
            eng.submit(SolveRequest(rid=i, band=jnp.asarray(bands[i]),
                                    b=jnp.asarray(bs[i]),
                                    fingerprint=f"{i}.{version[i]}"))
        done = sorted(eng.run_until_drained(), key=lambda r: r.rid)
        steps.append({"bands": bands, "bs": bs, "done": done,
                      "delta": {c: eng.stats[c] - before[c] for c in COUNTERS}})
    return steps


def test_time_stepping_fleet_matches_dense_solves(fleet):
    from repro.core.banded import band_to_dense

    for st in fleet:
        for band, b, r in zip(st["bands"], st["bs"], st["done"]):
            dense = np.asarray(band_to_dense(jnp.asarray(band)), np.float64)
            x = np.linalg.solve(dense, b.astype(np.float64))
            # The engine accepts an answer whose f32 true residual is within
            # its guard, 10 * tol = 1e-5 (it escalates otherwise), and the
            # forward error is at most cond(A) times the relative residual.
            err = np.linalg.norm(r.result.x - x) / np.linalg.norm(x)
            assert err <= np.linalg.cond(dense) * 1e-5
            assert r.result.converged and not r.result.escalated


def test_time_stepping_fleet_counters(fleet):
    first, *steps = fleet
    assert first["delta"]["factored_systems"] == SYSTEMS
    assert len(steps) == STEPS
    for st in steps:
        d, its = st["delta"], [r.result.iterations for r in st["done"]]
        assert d["steps"] == 1  # one batch a step
        assert d["factored_systems"] == d["cache_misses"] == REFRESH
        assert d["cache_hits"] == SYSTEMS - REFRESH
        assert [r.result.cache_hit for r in st["done"]].count(False) == REFRESH
        assert d["escalations"] == 0
        assert d["krylov_lane_max_total"] == max(its)
        assert d["krylov_iters_total"] / SYSTEMS == pytest.approx(np.mean(its))
        assert d["krylov_lane_max_total"] >= d["krylov_iters_total"] / SYSTEMS


def test_time_stepping_fleet_applies_counter(fleet):
    """krylov_applies_total grows by bicgstab2_applies at the batch's
    slowest lane: the applies the vmapped loop runs."""
    for st in fleet:
        its = [r.result.iterations for r in st["done"]]
        assert st["delta"]["krylov_applies_total"] == bicgstab2_applies(max(its))
        assert st["delta"]["krylov_applies_total"] >= 2
