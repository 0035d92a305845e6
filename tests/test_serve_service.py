"""AsyncSolverService: futures, scheduling, admission control, metrics."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SaPOptions
from repro.core.banded import band_matvec, oscillatory_banded, random_banded
from repro.serve import (
    AsyncSolverService,
    Cancelled,
    MetricsRegistry,
    QueueFull,
    SolveCancelled,
    band_dominance,
)
from repro.serve.metrics import Counter, Gauge, Histogram


def _mat(n, k, seed, d=1.1):
    return np.float32(random_banded(n, k, d=d, seed=seed))


def _rhs_for(band, seed):
    n = band.shape[0]
    x = np.random.default_rng(seed).normal(size=n)
    b = np.asarray(band_matvec(jnp.asarray(band), jnp.asarray(x, jnp.float32)))
    return x, b


def _opts(**kw):
    kw.setdefault("p", 4)
    kw.setdefault("variant", "C")
    kw.setdefault("tol", 1e-6)
    kw.setdefault("maxiter", 300)
    return SaPOptions(**kw)


def _service(**kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("start", False)
    return AsyncSolverService(_opts(), **kw)


# -- metrics primitives -----------------------------------------------------


def test_metrics_counter_and_histogram():
    reg = MetricsRegistry()
    c = reg.counter("reqs")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    h = reg.histogram("lat", bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4 and snap["max"] == 5.0
    assert h.quantile(0.5) == 1.0  # upper edge of the median's bucket
    assert reg.counter("reqs") is c  # get-or-create is idempotent
    with pytest.raises(ValueError):
        reg.gauge("reqs")  # name collision across types
    with pytest.raises(ValueError):
        reg.histogram("lat", bounds=(1.0, 2.0))  # re-register w/ new bounds
    full = reg.snapshot()
    assert full["counters"]["reqs"] == 3
    assert full["histograms"]["lat"]["count"] == 4


def test_gauge_set_inc_dec():
    g = Gauge("depth")
    assert g.value == 0.0
    g.set(5)
    g.inc()
    g.inc(2.5)
    g.dec()
    g.dec(0.5)
    assert g.value == 7.0
    g.dec(10)  # a level may go negative, unlike a counter
    assert g.value == -3.0
    g.set(1)
    assert g.value == 1.0 and isinstance(g.value, float)


def test_gauge_set_max_is_a_watermark():
    g = Gauge("peak_device_bytes")
    for v in (3, 9, 4):
        g.set_max(v)
    assert g.value == 9.0
    g.set(2)  # set() lowers it, set_max() never does
    g.set_max(1)
    assert g.value == 2.0


def test_gauge_snapshot_in_registry():
    reg = MetricsRegistry()
    g = reg.gauge("pending_now")
    assert reg.gauge("pending_now") is g
    g.set(4)
    assert g.snapshot() == 4.0
    assert reg.snapshot()["gauges"] == {"pending_now": 4.0}
    g.dec()
    assert reg.snapshot()["gauges"]["pending_now"] == 3.0
    with pytest.raises(ValueError):
        reg.counter("pending_now")  # name already used by the gauge


def test_metrics_thread_safety():
    c = Counter("c")
    h = Histogram("h", bounds=(0.5,))

    def spin():
        for _ in range(1000):
            c.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=spin) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000 and h.sum == pytest.approx(2000.0)


# -- futures + correctness --------------------------------------------------


def test_futures_resolve_with_correct_solutions():
    svc = _service(start=True)
    try:
        futs, truth = [], []
        for i in range(5):
            band = _mat(150 + 37 * i, 3 + i % 2, seed=i)
            x, b = _rhs_for(band, seed=50 + i)
            futs.append(svc.submit(band, b))
            truth.append(x)
        for fut, x in zip(futs, truth):
            out = fut.result(timeout=180)
            assert fut.done() and not fut.cancelled()
            assert out.converged
            assert out.x.shape == x.shape
            assert np.linalg.norm(out.x - x) / np.linalg.norm(x) < 1e-3
    finally:
        svc.close()
    assert svc.metrics.counter("solved").value == 5
    assert svc.snapshot()["derived"]["solves_per_second"] > 0


def test_future_timeout_then_resolution():
    svc = _service(start=False)  # no drain thread: nothing resolves
    band = _mat(100, 3, seed=0)
    fut = svc.submit(band, _rhs_for(band, seed=0)[1])
    with pytest.raises(TimeoutError):
        fut.result(timeout=0.01)
    assert not fut.done()
    assert svc.drain_once() == 1
    assert fut.result(timeout=0).converged
    svc.close()


# -- deadline / priority scheduling -----------------------------------------


def test_deadline_shedding_deterministic():
    svc = _service(start=False)
    band = _mat(100, 3, seed=0)
    _, b = _rhs_for(band, seed=0)
    doomed = svc.submit(band, b, deadline_s=0.0)
    alive = svc.submit(band, b, deadline_s=60.0)
    time.sleep(0.002)  # let the zero deadline lapse
    resolved = svc.drain_once()  # shed happens before dispatch
    assert resolved == 1
    assert doomed.cancelled()
    assert doomed.outcome() == Cancelled("deadline")
    with pytest.raises(SolveCancelled, match="deadline"):
        doomed.result(timeout=0)
    assert alive.result(timeout=0).converged
    assert svc.metrics.counter("deadline_misses").value == 1
    assert svc.engine.stats_snapshot()["solved"] == 1  # no wasted batch slot
    svc.close()


def test_default_deadline_from_config_applies():
    svc = _service(start=False, default_deadline_s=0.0)
    band = _mat(100, 3, seed=0)
    fut = svc.submit(band, _rhs_for(band, seed=0)[1])  # no explicit deadline
    time.sleep(0.002)
    svc.drain_once()
    assert fut.cancelled() and fut.outcome() == Cancelled("deadline")
    svc.close()


def test_priority_beats_fifo_and_edf_breaks_ties():
    svc = _service(start=False, max_batch=1)
    small = _mat(100, 3, seed=1)  # one bucket
    big = _mat(600, 3, seed=2)  # a different bucket
    _, bs = _rhs_for(small, seed=0)
    _, bb = _rhs_for(big, seed=0)
    low = svc.submit(small, bs, priority=0)
    late = svc.submit(big, bb, priority=5, deadline_s=600.0)
    soon = svc.submit(big, bb, priority=5, deadline_s=60.0)
    svc.drain_once()  # the high-priority bucket dispatches first...
    assert soon.done() and not late.done() and not low.done()  # ...EDF first
    svc.drain_once()
    assert late.done() and not low.done()
    svc.drain_once()
    assert low.done()
    svc.close()


def test_client_cancel_before_scheduling():
    svc = _service(start=False)
    band = _mat(100, 3, seed=0)
    fut = svc.submit(band, _rhs_for(band, seed=0)[1])
    assert fut.cancel()
    svc.drain_once()
    assert fut.cancelled() and fut.outcome() == Cancelled("client")
    assert svc.engine.stats_snapshot()["solved"] == 0
    assert svc.metrics.counter("client_cancels").value == 1
    svc.close()


# -- admission control -------------------------------------------------------


def test_queue_full_raises_without_blocking():
    svc = _service(start=False, queue_cap=2)
    band = _mat(100, 3, seed=0)
    _, b = _rhs_for(band, seed=0)
    svc.submit(band, b, block=False)
    svc.submit(band, b, block=False)
    with pytest.raises(QueueFull):
        svc.submit(band, b, block=False)
    assert svc.metrics.counter("queue_rejections").value == 1
    with pytest.raises(QueueFull):  # blocking with a timeout also bounds
        svc.submit(band, b, timeout=0.02)
    svc.close(drain=False)


def test_backpressure_unblocks_when_drained():
    svc = _service(start=True, queue_cap=2, max_batch=8)
    band = _mat(100, 3, seed=0)
    _, b = _rhs_for(band, seed=0)
    futs = [svc.submit(band, b, timeout=180) for _ in range(6)]
    for fut in futs:  # every blocked submit eventually got a slot
        assert fut.result(timeout=180).converged
    svc.close()


def test_close_without_drain_sheds_pending():
    svc = _service(start=False)
    band = _mat(100, 3, seed=0)
    fut = svc.submit(band, _rhs_for(band, seed=0)[1])
    svc.close(drain=False)
    assert fut.cancelled() and fut.outcome() == Cancelled("shutdown")
    with pytest.raises(RuntimeError):
        svc.submit(band, _rhs_for(band, seed=0)[1])


# -- dominance-class routing -------------------------------------------------


def test_band_dominance_host_estimator_matches_policy():
    dom = _mat(128, 3, seed=0, d=1.5)
    osc = np.float32(oscillatory_banded(128, 3, d=0.5, seed=0))
    assert band_dominance(dom) >= 1.0
    assert band_dominance(osc) < 1.0
    eye = np.zeros((8, 7), np.float32)
    eye[:, 3] = 1.0
    assert band_dominance(eye) == np.inf


def test_requests_route_to_per_class_variants():
    svc = AsyncSolverService(
        _opts(variant="auto", maxiter=400), max_batch=8, start=False
    )
    # k=3 rounds up to the bucket K=4: the interleaved identity-row
    # embedding keeps E exact on the ill-conditioned matrix, so no
    # K-pinning workaround is needed anymore
    dom = _mat(128, 3, seed=0, d=1.5)
    osc = np.float32(oscillatory_banded(128, 3, d=0.5, seed=1))
    _, bd = _rhs_for(dom, seed=0)
    _, bo = _rhs_for(osc, seed=1)
    fd = svc.submit(dom, bd)
    fo = svc.submit(osc, bo)
    svc.drain_once()
    svc.drain_once()
    rd, ro = fd.result(timeout=0), fo.result(timeout=0)
    assert rd.variant == "C" and rd.converged  # d >= 1: truncated SPIKE
    assert ro.variant == "E" and ro.converged  # d < 1: exact reduced system
    assert np.isfinite(rd.true_resnorm) and np.isfinite(ro.true_resnorm)
    assert not ro.misconverged  # the PR 6 silent-failure mode stays dead
    # the oscillatory matrix is ill-conditioned: check the residual, not
    # the distance to the generating x (which f32 noise amplifies)
    res = np.asarray(
        band_matvec(jnp.asarray(osc), jnp.asarray(ro.x, jnp.float32))
    ) - bo
    assert np.linalg.norm(res) / np.linalg.norm(bo) < 1e-3
    svc.close()


def test_class_override_must_keep_p():
    with pytest.raises(ValueError, match="changes p"):
        AsyncSolverService(
            _opts(p=4),
            class_overrides={"dom": _opts(p=8)},
            start=False,
        )


# -- LRU thrash guard --------------------------------------------------------


def test_thrash_guard_widens_rounding():
    svc = _service(
        start=False,
        rounding="exact",
        cache_size=1,
        thrash_window=4,
        thrash_ratio=0.25,
    )
    # distinct matrices over distinct exact shapes: every solve misses and
    # evicts the previous entry -> eviction rate ~1 per solve
    for i in range(6):
        band = _mat(96 + 4 * i, 3, seed=i)
        svc.submit(band, _rhs_for(band, seed=i)[1])
    while svc.pending:
        svc.drain_once()
    assert svc.rounding == "pow2"
    assert svc.metrics.counter("rounding_widenings").value == 1
    # new arrivals now share pow2 buckets
    band = _mat(97, 3, seed=99)
    fut = svc.submit(band, _rhs_for(band, seed=99)[1])
    svc.drain_once()
    assert fut.result(timeout=0).bucket[0] == 256
    svc.close()


# -- the concurrent soak -----------------------------------------------------


def test_soak_concurrent_mixed_priorities_and_deadlines():
    """N client threads, mixed priorities/deadlines: every future must
    resolve -- solved, shed, or cancelled -- and never hang."""
    svc = AsyncSolverService(
        _opts(variant="auto"), max_batch=8, queue_cap=64, start=True
    )
    n_threads, per_thread = 4, 6
    futs_by_thread = [[] for _ in range(n_threads)]
    errors = []

    def client(tid):
        try:
            rng = np.random.default_rng(tid)
            for j in range(per_thread):
                i = tid * per_thread + j
                band = _mat(100 + 25 * (i % 4), 3, seed=i % 5)
                b = rng.normal(size=band.shape[0]).astype(np.float32)
                # a few impossible deadlines force the shed path under load
                deadline = 0.0 if (i % 7 == 3) else 120.0
                fut = svc.submit(
                    band, b, priority=i % 3, deadline_s=deadline,
                    timeout=120,
                )
                futs_by_thread[tid].append(fut)
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [
        threading.Thread(target=client, args=(tid,))
        for tid in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
        assert not t.is_alive(), "client thread hung on submit"
    assert not errors
    solved = shed = 0
    for futs in futs_by_thread:
        assert len(futs) == per_thread
        for fut in futs:
            out = fut.outcome(timeout=180)  # never hangs
            if isinstance(out, Cancelled):
                assert out.reason in ("deadline", "shutdown")
                shed += 1
            else:
                assert out.converged
                solved += 1
    assert solved + shed == n_threads * per_thread
    assert solved > 0
    svc.close()
    snap = svc.snapshot()
    assert snap["counters"]["solved"] == solved
    assert snap["counters"]["deadline_misses"] == shed
    assert snap["histograms"]["time_in_queue_s"]["count"] == solved
    assert snap["histograms"]["queue_depth"]["count"] == solved + shed
    assert snap["engine"]["solved"] == solved
