"""Tracer semantics, Chrome-trace schema, Prometheus exposition, overhead."""

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmarks.check_trace import (
    TraceError,
    check_bench_stages,
    check_required,
    validate_events,
)
from repro.core import SaPOptions
from repro.core.banded import random_banded
from repro.obs import NULL_SPAN, Tracer, get_tracer, span, use_tracer
from repro.serve import AsyncSolverService, SolverEngine
from repro.serve.metrics import DEFAULT_BOUNDS, Histogram, MetricsRegistry

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_span_nesting_and_attrs():
    tr = Tracer()
    with tr.span("outer", n=4) as sp:
        time.sleep(0.001)
        with tr.span("inner") as child:
            child.annotate(hits=2)
        sp.annotate(done=True)
    (root,) = tr.roots()
    assert root.name == "outer"
    assert root.attrs == {"n": 4, "done": True}
    assert [c.name for c in root.children] == ["inner"]
    assert root.children[0].attrs == {"hits": 2}
    assert root.duration_s >= 0.001
    assert root.duration_s >= root.children[0].duration_s
    assert tr.find("inner") and tr.durations()["outer"] == root.duration_s


def test_disabled_tracer_returns_null_span():
    tr = Tracer(enabled=False)
    sp = tr.span("x", a=1)
    assert sp is NULL_SPAN
    assert not sp  # falsy: guards `if sp: sp.annotate(...)` call sites
    with sp:
        assert sp.sync("v") == "v"
        sp.annotate(b=2)
    assert tr.roots() == []


def test_module_span_without_active_tracer_is_null():
    assert get_tracer() is None
    assert span("anything") is NULL_SPAN


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation`` with the profiler on:
    records each annotation's enter and exit with its thread."""

    log: list = []

    def __init__(self, name):
        self.name = name

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        self.log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, threading.get_ident()))
        return False


@pytest.fixture
def annotations(monkeypatch):
    _Annotations.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotations)
    return _Annotations.log


class _NoBlock:
    """A pytree leaf that fails if anything blocks on it."""

    def block_until_ready(self):
        raise AssertionError("the span blocked")


def test_span_without_tracer_annotates_and_never_blocks(annotations):
    assert get_tracer() is None
    value = _NoBlock()
    with span("factor", n=4) as sp:
        assert not sp  # falsy: `if sp:` attribute work stays off
        assert sp.sync(value) is value
        sp.annotate(variant="C")
    # the name carries no attributes
    assert [(k, n) for k, n, _ in annotations] == [("enter", "sap.factor"),
                                                   ("exit", "sap.factor")]


def test_span_without_tracer_or_profiler_is_null():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert span("factor") is NULL_SPAN


def test_span_under_jit_is_null_with_profiler_on(annotations):
    import jax.numpy as jnp

    seen = []

    @jax.jit
    def f(x):
        with span("inside") as sp:
            seen.append(sp)
        return x + 1.0

    assert float(f(jnp.float32(1.0))) == 2.0
    assert seen == [NULL_SPAN]
    assert not [n for _, n, _ in annotations if n == "sap.inside"]


@pytest.mark.parametrize("enabled", [True, False])
def test_tracer_span_records_and_annotates(annotations, enabled):
    tr = Tracer(enabled=enabled)
    with use_tracer(tr):
        with span("factor") as sp:
            with span("factor.split"):
                pass
    assert bool(sp) is enabled
    assert [(k, n) for k, n, _ in annotations] == [
        ("enter", "sap.factor"), ("enter", "sap.factor.split"),
        ("exit", "sap.factor.split"), ("exit", "sap.factor")]
    if enabled:
        (root,) = tr.roots()
        assert [c.name for c in root.children] == ["factor.split"]
    else:
        assert tr.roots() == []


def test_compile_marker_lands_inside_the_open_span(annotations):
    import jax.numpy as jnp

    from repro.obs.cost import COMPILES, install_compile_listener

    assert install_compile_listener()
    count0 = COMPILES.totals()[0]
    with span("factor.reduced"):
        jax.jit(lambda x: x * 3.0 - 1.0)(jnp.arange(5.0)).block_until_ready()
    assert COMPILES.totals()[0] > count0
    names = [n for _, n, _ in annotations]
    assert names[0] == "sap.factor.reduced" and names[-1] == "sap.factor.reduced"
    inner = names[1:-1]
    assert inner and set(inner) == {"sap.backend_compile"}
    assert inner == ["sap.backend_compile"] * len(inner)
    assert len({tid for _, _, tid in annotations}) == 1  # on the compiling thread


@pytest.mark.parametrize("kernel,names", [
    ("bts", ["sap_bts_forward", "sap_bts_backward"]),
    ("btf", ["sap_btf"]),
    ("fused", ["sap_fused_factor_spike"]),
    ("bcr_factor", ["sap_bcr_inv_odd", "sap_bcr_reduce"]),
    ("bcr_solve", ["sap_bcr_rhs_reduce", "sap_bcr_backsub"]),
])
def test_solver_kernels_have_stable_names(kernel, names):
    import jax.numpy as jnp
    from jax.extend import core as jcore

    from repro.kernels.bcr import bcr_factor_pallas, bcr_solve_pallas
    from repro.kernels.btf import btf_pallas
    from repro.kernels.bts import bts_pallas
    from repro.kernels.fused_spike import fused_factor_spike_pallas

    p, m, k, r = 2, 4, 8, 2
    blk = jnp.zeros((p, m, k, k), jnp.float32)
    corner = jnp.zeros((p, k, k), jnp.float32)
    chain = jnp.broadcast_to(jnp.eye(k, dtype=jnp.float32), (m, k, k))
    calls = {
        "bts": lambda: bts_pallas(blk, blk, blk, jnp.zeros((p, m, k, r)), interpret=True),
        "btf": lambda: btf_pallas(blk + jnp.eye(k), blk, blk, interpret=True),
        "fused": lambda: fused_factor_spike_pallas(
            blk + jnp.eye(k), blk, blk, corner, corner, interpret=True),
        "bcr_factor": lambda: bcr_factor_pallas(chain, 0 * chain, 0 * chain,
                                                interpret=True),
        "bcr_solve": lambda: bcr_solve_pallas(
            bcr_factor_pallas(chain, 0 * chain, 0 * chain, interpret=True),
            jnp.zeros((m, k, r)), interpret=True),
    }
    jaxpr = jax.make_jaxpr(calls[kernel])()

    def called(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for v in eqn.params.values():
                if isinstance(v, jcore.ClosedJaxpr):
                    yield from called(v.jaxpr)
                elif isinstance(v, jcore.Jaxpr):
                    yield from called(v)

    found = list(called(jaxpr.jaxpr))
    # the factor of the solve's chain is traced too; keep the solve's own
    assert set(names) <= set(found)
    assert all(n.startswith("sap_") for n in found)


def test_use_tracer_nests_and_restores():
    t1, t2 = Tracer(), Tracer()
    with use_tracer(t1):
        assert get_tracer() is t1
        with use_tracer(t2):
            assert get_tracer() is t2
            with span("on-t2"):
                pass
        assert get_tracer() is t1
    assert get_tracer() is None
    assert [s.name for s in t2.roots()] == ["on-t2"]
    assert t1.roots() == []


def test_thread_safety_per_thread_stacks():
    tr = Tracer()

    def worker(i):
        with tr.span(f"w{i}"):
            with tr.span("child"):
                time.sleep(0.001)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    roots = tr.roots()
    assert len(roots) == 8  # one root per thread, never cross-adopted
    assert {r.name for r in roots} == {f"w{i}" for i in range(8)}
    assert all(len(r.children) == 1 for r in roots)
    # NOTE: don't assert 8 distinct tids -- the OS recycles thread idents
    # when an early worker exits before a later one starts


def test_record_retroactive_span():
    tr = Tracer()
    t0 = tr.now()
    time.sleep(0.001)
    tr.record("request", t0, tr.now(), rid=7)
    (root,) = tr.roots()
    assert root.name == "request" and root.attrs["rid"] == 7
    assert root.duration_s >= 0.001


def test_summary_tree():
    tr = Tracer()
    with tr.span("solve"):
        with tr.span("factor"):
            pass
        with tr.span("krylov"):
            pass
    text = tr.summary()
    assert "solve" in text and "  factor" in text and "  krylov" in text
    assert "% parent" in text


# ---------------------------------------------------------------------------
# Chrome trace export + validator
# ---------------------------------------------------------------------------


def _traced_forest():
    tr = Tracer()
    with tr.span("a", nan=float("nan")):
        with tr.span("b"):
            pass
    # overlapping retroactive spans (the serve.request pattern)
    t = tr.now()
    tr.record("req", t - 0.01, t - 0.002)
    tr.record("req", t - 0.008, t - 0.001)
    return tr


def test_chrome_events_validate(tmp_path):
    tr = _traced_forest()
    events = tr.to_chrome_events()
    pairs = validate_events(events)
    assert pairs == {"a": 1, "b": 1, "req": 2}
    check_required(pairs, ["a", "b"])
    with pytest.raises(TraceError):
        check_required(pairs, ["missing-span"])
    # NaN attrs must still produce strict JSON
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    doc = json.loads(Path(path).read_text())
    assert validate_events(doc["traceEvents"])
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"process_name", "thread_name"} <= names


def test_validator_rejects_unbalanced():
    with pytest.raises(TraceError):
        validate_events(
            [{"name": "x", "ph": "B", "pid": 1, "tid": 1, "ts": 0.0}]
        )
    with pytest.raises(TraceError):
        validate_events(
            [{"name": "x", "ph": "E", "pid": 1, "tid": 1, "ts": 0.0}]
        )
    with pytest.raises(TraceError):
        validate_events([{"name": "x", "ph": "B", "tid": 1, "ts": 0.0}])


def test_check_bench_stages(tmp_path):
    good = tmp_path / "good.json"
    good.write_text(json.dumps(
        {"rows": [{"name": "r", "stages": {"lu_spk": 0.6, "krylov": 0.4}}]}
    ))
    assert check_bench_stages(good) == 1
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        {"rows": [{"name": "r", "stages": {"lu_spk": 0.4, "krylov": 0.4}}]}
    ))
    with pytest.raises(TraceError):
        check_bench_stages(bad)
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"rows": [{"name": "r"}]}))
    with pytest.raises(TraceError):
        check_bench_stages(empty)


def test_traced_solve_example_smoke(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "examples" / "traced_solve.py"),
         "--smoke", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr
    doc = json.loads((tmp_path / "trace.json").read_text())
    pairs = validate_events(doc["traceEvents"])
    check_required(
        pairs, ["reorder", "factor.lu", "factor.spike", "krylov"]
    )


# ---------------------------------------------------------------------------
# lifecycle spans land on the active tracer
# ---------------------------------------------------------------------------


def test_engine_spans_and_stage_split():
    import jax.numpy as jnp

    from repro.core import batch_factor, batch_plan

    opts = SaPOptions(p=4, variant="C", tol=1e-6)
    bands = [np.float32(random_banded(256, 4, d=1.1, seed=s)) for s in (0, 1)]
    bmat = jnp.stack([
        np.random.default_rng(s).normal(size=256).astype(np.float32)
        for s in (0, 1)
    ])
    tr = Tracer()
    with use_tracer(tr):
        bfac = batch_factor(batch_plan(bands, opts))
        bfac.solve_batch(bmat)
    names = {s.name for s in tr.walk()}
    assert {"factor.batch", "krylov"} <= names
    kr = tr.find("krylov")[0]
    conv = kr.attrs["convergence"]
    assert conv["converged"] is True and conv["iterations"] > 0

    from benchmarks.common import stage_fractions

    stages = stage_fractions(tr)
    assert set(stages) == {"lu_spk", "krylov"}
    assert sum(stages.values()) == pytest.approx(1.0, abs=0.02)
    # and a tracer with no mapped spans yields None, not a bogus dict
    assert stage_fractions(Tracer()) is None


def test_service_request_spans():
    svc = AsyncSolverService(
        SaPOptions(p=4, variant="C", tol=1e-6), max_batch=4, start=False
    )
    try:
        band = np.float32(random_banded(256, 4, d=1.1, seed=0))
        rng = np.random.default_rng(0)
        tr = Tracer()
        with use_tracer(tr):
            futs = [
                svc.submit(band, rng.normal(size=256).astype(np.float32))
                for _ in range(3)
            ]
            while svc.drain_once():
                pass
        assert all(f.result(timeout=1).converged for f in futs)
        # one dispatch span wrapping the engine span, plus one retroactive
        # serve.request root per request covering submit -> resolve
        (disp,) = tr.find("serve.dispatch")
        assert disp.attrs["batch"] == 3
        assert [c.name for c in disp.children] == ["engine.solve_prepared"]
        reqs = tr.find("serve.request")
        assert len(reqs) == 3
        for sp in reqs:
            assert sp.duration_s >= disp.duration_s * 0.5
            assert "queue_s" in sp.attrs and "cache_hit" in sp.attrs
        # and the export of overlapping retroactive spans stays valid
        assert validate_events(tr.to_chrome_events())["serve.request"] == 3
    finally:
        svc.close()


def test_disabled_overhead_under_two_percent():
    """Null-span cost per solve_prepared call < 2% of the warm solve time."""
    eng = SolverEngine(
        SaPOptions(p=4, variant="C", tol=1e-6), max_batch=8, cache_size=16
    )
    band = np.float32(random_banded(256, 4, d=1.1, seed=0))
    rng = np.random.default_rng(0)

    def one_pass():
        from repro.core.batched import bucket_shape

        b = rng.normal(size=256).astype(np.float32)
        from repro.serve.solver_engine import SolveRequest

        req = SolveRequest(rid=0, band=band, b=b)
        bkt = bucket_shape(256, 4, 4, "pow2")
        eng.solve_prepared([req], bkt)

    one_pass()  # warm the jit caches
    t0 = time.perf_counter()
    for _ in range(5):
        one_pass()
    warm_solve_s = (time.perf_counter() - t0) / 5

    # per-site cost of an instrumented span with tracing off: a disabled
    # tracer installed, and no tracer at all; the profiler is off in both
    assert not jax.profiler.TraceAnnotation.is_enabled()
    for tracer in (Tracer(enabled=False), None):
        with use_tracer(tracer):
            n = 10_000
            t0 = time.perf_counter()
            for _ in range(n):
                with span("engine.solve_prepared", bucket="256x4", batch=1):
                    pass
            per_site_s = (time.perf_counter() - t0) / n
        # the hot path crosses a handful of span sites per solve; even 10x
        # that stays far under the 2% budget
        assert per_site_s * 10 < 0.02 * warm_solve_s, (
            f"null-span overhead {per_site_s * 1e9:.0f} ns/site vs warm solve "
            f"{warm_solve_s * 1e6:.0f} us (tracer {tracer})"
        )


# ---------------------------------------------------------------------------
# metrics: quantile edges + prometheus exposition
# ---------------------------------------------------------------------------


def test_quantile_edges():
    h = Histogram("t", bounds=(1.0, 2.0, 4.0))
    assert np.isnan(h.quantile(0.0))
    assert np.isnan(h.quantile(0.5))
    assert np.isnan(h.quantile(1.0))
    for v in (0.5, 1.5, 3.0, 9.0):
        h.observe(v)
    assert h.quantile(0.0) == 0.5  # exact observed min, not a bucket edge
    assert h.quantile(1.0) == 9.0  # exact observed max (overflow bucket)
    assert h.quantile(0.5) == 2.0  # upper edge of the rank-2 bucket
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("requests").inc(3)
    reg.counter("shed_total").inc()  # already suffixed: not doubled
    reg.gauge("queue-depth.now").set(5)
    h = reg.histogram("latency_s", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    text = reg.to_prometheus(prefix="sap_")
    lines = text.splitlines()
    assert "# TYPE sap_requests_total counter" in lines
    assert "sap_requests_total 3" in lines
    assert "sap_shed_total 1" in lines
    assert text.count("shed_total_total") == 0
    assert "sap_queue_depth_now 5" in lines  # sanitized name
    assert 'sap_latency_s_bucket{le="0.1"} 1' in lines
    assert 'sap_latency_s_bucket{le="1"} 2' in lines  # cumulative
    assert 'sap_latency_s_bucket{le="+Inf"} 3' in lines
    assert "sap_latency_s_sum 2.55" in lines
    assert "sap_latency_s_count 3" in lines
    assert text.endswith("\n")


def test_service_render_and_hist_bounds():
    bounds = (0.01, 0.1, 1.0)
    svc = AsyncSolverService(
        SaPOptions(p=4, variant="C", tol=1e-6),
        max_batch=4,
        hist_bounds=bounds,
        start=False,
    )
    try:
        assert svc.metrics.histogram("time_in_queue_s").bounds == bounds
        text = svc.render()
        assert "# TYPE" in text and "time_in_queue_s" in text
    finally:
        svc.close()
    # default bounds when not overridden
    svc2 = AsyncSolverService(
        SaPOptions(p=4, variant="C", tol=1e-6), max_batch=4, start=False
    )
    try:
        assert (
            svc2.metrics.histogram("time_in_queue_s").bounds == DEFAULT_BOUNDS
        )
    finally:
        svc2.close()


def test_solver_config_hist_bounds_roundtrip():
    from repro.configs.sap_solver import SolverConfig

    cfg = SolverConfig(name="t", n=512, k=8, hist_bounds=(0.5, 5.0))
    svc = cfg.to_service(p=4, start=False)
    try:
        assert svc.metrics.histogram("time_in_queue_s").bounds == (0.5, 5.0)
    finally:
        svc.close()


def test_engine_time_split_stats():
    eng = SolverEngine(SaPOptions(p=4, variant="C", tol=1e-6), max_batch=8)
    band = np.float32(random_banded(256, 4, d=1.1, seed=0))
    rng = np.random.default_rng(0)
    for _ in range(3):
        eng.submit_system(band, rng.normal(size=256).astype(np.float32))
    eng.run_until_drained()
    st = eng.stats_snapshot()
    assert st["factor_seconds_total"] > 0.0  # one miss was factored
    assert st["solve_seconds_total"] > 0.0
    assert st["solve_seconds"] == pytest.approx(
        st["factor_seconds_total"] + st["solve_seconds_total"], rel=1e-6
    )
    assert eng.systems_per_second > 0.0
