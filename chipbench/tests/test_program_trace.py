"""The reduction of the program's own spans in a profiler trace, and the
reader of the fused kernel's device time."""

import json

import pytest

from chipbench import harness, program_trace, tracing
from chipbench.tests.conftest import ROOT
from chipbench.tracing import Event

TPU0, TPU1, OPS, MODULES = "/device:TPU:0", "/device:TPU:1", "XLA Ops", "XLA Modules"
MAIN, OTHER = "0:python3", "1:python3"
NS = 1e-9


def host(name, s, e, line=MAIN):
    return Event("/host:CPU", line, name, float(s), float(e - s))


def op(name, s, e, plane=TPU0):
    return Event(plane, OPS, name, float(s), float(e - s))


def module(name, s, e, plane=TPU0):
    return Event(plane, MODULES, name, float(s), float(e - s))


# One step: factor [10, 60] holds dominance [10, 14], fused [15, 30] and
# reduced [32, 58]; krylov [62, 96].  A compile happens inside reduced and
# one outside any span; a span on another thread never adopts the marker.
HAND = [
    host("bench.window", 0, 100),
    host("bench.factor", 8, 61),
    host("sap.factor", 10, 60),
    host("sap.factor.dominance", 10, 14),
    host("sap.factor.fused", 15, 30),
    host("sap.factor.reduced", 32, 58),
    host("sap.backend_compile", 40, 40),
    host("sap.backend_compile", 99, 99),
    host("sap.krylov", 62, 96),
    host("sap.worker", 35, 45, line=OTHER),
    op("while.86", 0, 9),
    op("reduce.1", 11, 13),
    op("%sap_fused_factor_spike.1 = f32[8] custom-call(x)", 16, 50),
    op("fusion.3", 52, 55),
    op("while.87", 64, 90),
    op("sap_bts_forward.107", 66, 70), op("sap_bts_backward.108", 70, 75),
    op("sap_bts_forward.121", 80, 82),
    op("copy.1", 97, 98),
    module("jit__solve_many(3)", 0, 9),
    module("jit_dominance(0)", 11, 13),
    module("jit_fused_factor_spike_pallas(1)", 16, 50),
    module("jit_gj(2)", 52, 55),
    module("jit__solve_many(3)", 64, 90),
]


def test_spans_time_and_nesting():
    r = program_trace.reduce(HAND)
    s = {k.removeprefix("sap."): v for k, v in r["spans"].items()}
    assert set(s) == {"bench.factor", "factor", "factor.dominance", "factor.fused",
                      "factor.reduced", "krylov", "worker"}
    assert s["factor"]["count"] == 1
    assert s["factor"]["seconds"] == pytest.approx(50 * NS)
    # the children cover 4 + 15 + 26 of the factor's 50
    assert s["factor"]["self_s"] == pytest.approx(5 * NS)
    assert s["factor.reduced"]["self_s"] == pytest.approx(26 * NS)
    assert s["bench.factor"]["self_s"] == pytest.approx(3 * NS)
    assert s["factor"]["device_s"] == pytest.approx((2 + 34 + 3) * NS)
    assert r["busy_s"] == pytest.approx((9 + 2 + 34 + 3 + 26 + 1) * NS)


def test_idle_goes_to_the_span_that_covers_most_of_it():
    r = program_trace.reduce(HAND)
    s = {k.removeprefix("sap."): v for k, v in r["spans"].items()}
    # gaps [9,11]: bench.factor covers 2, factor and dominance 1;
    # [13,16]: bench.factor and factor 3 (the inner wins), dominance 1,
    # fused 1; [50,52]: bench.factor, factor and reduced 2 (reduced wins);
    # [55,64]: bench.factor 6, factor 5, reduced 3, krylov 2;
    # [90,97]: krylov 6; [98,100]: nothing
    assert s["bench.factor"]["idle_s"] == pytest.approx((2 + 9) * NS)
    assert s["factor.dominance"]["idle_s"] == 0
    assert s["factor"]["idle_s"] == pytest.approx(3 * NS)
    assert s["factor.reduced"]["idle_s"] == pytest.approx(2 * NS)
    assert s["factor.fused"]["idle_s"] == 0
    assert s["krylov"]["idle_s"] == pytest.approx(7 * NS)
    assert r["idle_by_span"]["no span"] == pytest.approx(2 * NS)
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_ties_go_to_the_innermost_span():
    events = [host("bench.window", 0, 10), host("sap.factor", 0, 10),
              host("sap.factor.split", 2, 6), op("a", 0, 3), op("b", 5, 10)]
    r = program_trace.reduce(events)
    assert r["idle_by_span"] == {"sap.factor.split": pytest.approx(2 * NS)}


def test_programs_compiles_and_kernels_per_span():
    r = program_trace.reduce(HAND)
    s = {k.removeprefix("sap."): v for k, v in r["spans"].items()}
    assert s["factor"]["programs"] == 3  # dominance, fused and jit_gj start inside
    assert s["factor.fused"]["programs"] == 1
    assert s["factor.dominance"]["programs"] == 1
    assert s["krylov"]["programs"] == 1
    assert s["factor.reduced"]["compiles"] == 1
    assert s["factor"]["compiles"] == 0 and s["factor"]["compiles_inside"] == 1
    assert s["bench.factor"]["compiles_inside"] == 1
    assert s["worker"]["compiles"] == 0  # another thread
    assert r["compiles"] == 2 and r["compiles_outside_spans"] == 1
    # .N suffixes stripped and instances summed, inside each span
    assert s["krylov"]["kernels"]["sap_bts_forward"] == pytest.approx((4 + 2) * NS)
    assert s["krylov"]["kernels"]["sap_bts_backward"] == pytest.approx(5 * NS)
    assert s["factor.fused"]["kernels"] == {"sap_fused_factor_spike": pytest.approx(14 * NS)}
    assert r["kernels"]["sap_fused_factor_spike"] == pytest.approx(34 * NS)


def test_events_are_clipped_to_the_window():
    r = program_trace.reduce(HAND, window=(20, 70))
    s = {k.removeprefix("sap."): v for k, v in r["spans"].items()}
    assert r["window_s"] == pytest.approx(50 * NS)
    assert "factor.dominance" not in s
    assert s["factor"]["seconds"] == pytest.approx(40 * NS)
    assert s["krylov"]["seconds"] == pytest.approx(8 * NS)
    assert r["kernels"]["sap_fused_factor_spike"] == pytest.approx(30 * NS)
    assert r["compiles"] == 1


def test_two_chips_average():
    events = HAND + [op("sap_fused_factor_spike.3", 16, 50, TPU1),
                     module("jit_fused_factor_spike_pallas(1)", 16, 50, TPU1)]
    r = program_trace.reduce(events)
    assert r["chips"] == 2
    assert r["kernels"]["sap_fused_factor_spike"] == pytest.approx(34 * NS)
    assert r["spans"]["sap.factor.fused"]["programs"] == 1


def test_step_metrics():
    r = program_trace.reduce(HAND)
    m = program_trace.step_metrics(r)
    # idle inside the factor: its 50 less the 39 the device is busy
    assert m["factor_idle_s"] == pytest.approx(11 * NS)
    assert m["factor_programs"] == 3
    assert m["factor_compiles"] == 1
    assert m["fused_kernel_s"] == pytest.approx(34 * NS)
    # the kernels over the window: a span closes when its work is dispatched
    assert m["precond_apply_s"] == pytest.approx(11 * NS)
    assert set(program_trace.step_metrics(None).values()) == {None}
    # a trace of a program without its spans (or its kernel names) reads nothing
    bare = [e for e in HAND if not e.name.startswith("sap.")]
    assert set(program_trace.step_metrics(program_trace.reduce(bare)).values()) == {None}


def test_the_benchmark_reduction_ignores_program_spans():
    bare = [e for e in HAND if not e.name.startswith("sap.")]
    assert tracing.reduce(HAND) == tracing.reduce(bare)


def test_kernel_and_host_names():
    assert program_trace.kernel_name("jit__solve_many:sap_bts_forward.109") == "sap_bts_forward"
    assert program_trace.kernel_name("%fusion.12 = f32[4] fusion(a)") == "fusion"
    # the trace's operations carry their HLO text, layouts and all
    hlo = ("%sap_bts_backward.60 = f32[4,16,63,200,1]{4,3,2,1,0:T(8,128)} custom-call("
           "f32[16,63,200,200]{3,2,1,0:T(8,128)} %sinv.1), custom_call_target=\"tpu_custom_call\"")
    assert program_trace.kernel_name(hlo) == "sap_bts_backward"
    assert program_trace.kernel_name("?:copy-start") == "copy-start"
    assert program_trace.host_name("sap.factor#n=4#") == "sap.factor"


def test_a_cpu_capture_holds_the_spans_and_compile_markers(tmp_path):
    """The program's spans reach a real profiler capture, on the thread
    that opened them, with the compile markers inside the span that compiled."""
    import jax
    import jax.numpy as jnp

    from repro.obs import span

    with jax.profiler.trace(str(tmp_path)):
        with span("factor"):
            with span("factor.reduced"):
                jax.jit(lambda x: 2.0 * x + 5.0)(jnp.arange(3.0)).block_until_ready()
    events = program_trace.load(tracing.find_xplane(str(tmp_path)))
    fac = [e for e in events if e.name == "sap.factor"]
    red = [e for e in events if e.name == "sap.factor.reduced"]
    marks = [e for e in events if e.name == "sap.backend_compile"]
    assert len(fac) == 1 and len(red) == 1 and marks
    assert red[0].line == fac[0].line
    assert fac[0].start_ns <= red[0].start_ns and red[0].end_ns <= fac[0].end_ns
    for m in marks:
        assert m.line == red[0].line and red[0].start_ns <= m.start_ns <= red[0].end_ns


STEP = ROOT / "chipbench" / "tests" / "data" / "program_step.json"


def test_recorded_chip_step():
    """One step of dense_c.fresh recorded on a TPU v5e: the factor's spans,
    its compile marker, and the kernels under their stable names."""
    events = tracing.read_events(str(STEP))
    r = program_trace.reduce(events)
    bench = tracing.reduce(events)
    assert r["busy_s"] == pytest.approx(bench["busy_s"], rel=1e-9)
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                                            rel=1e-6)
    s = r["spans"]
    assert {"sap.factor", "sap.factor.dominance", "sap.factor.split", "sap.factor.fused",
            "sap.factor.reduced", "sap.krylov"} <= set(s)
    assert s["sap.factor.reduced"]["compiles"] == 1 and r["compiles_outside_spans"] == 0
    # the factor's programs: all of them start inside the benchmark's span,
    # fewer inside the program's, which closes when its work is dispatched
    assert s["sap.factor"]["programs"] < s["bench.factor"]["programs"]
    assert {"sap_fused_factor_spike", "sap_bts_forward", "sap_bts_backward"} <= set(r["kernels"])
    m = program_trace.step_metrics(r)
    assert m["fused_kernel_s"] > s["sap.factor"]["device_s"]
    assert all(v is not None and v > 0 for v in m.values())
    read = harness.load_reader("fused_kernel_s.dense")
    assert read({"trace": bench}) == pytest.approx(m["fused_kernel_s"])


READER = "fused_kernel_s.dense"


def _rec(device_ops, factors=2):
    return {"trace": {"device_ops": device_ops, "span_count": {"factor": factors}},
            "steps": [{}] * factors}


def test_fused_kernel_reader():
    read = harness.load_reader(READER)
    assert read({"steps": []}) is None
    assert read({"trace": None}) is None
    ops = [["jit_fused_factor_spike_pallas:sap_fused_factor_spike.1", 4.0],
           ["jit__solve_many:while.87", 3.0],
           ["_:sap_fused_factor_spike.1", 0.5],
           ["jit__solve_many:sap_bts_forward.109", 0.2]]
    assert read(_rec(ops)) == pytest.approx(4.5 / 2)
    # the kernel under another name (the parent's) is not read
    assert read(_rec([["jit_fused_factor_spike_pallas:fused_factor_spike_pallas.1", 4.0]])) is None
    assert read(_rec(ops, factors=0)) is None


def test_fused_kernel_metric_is_declared():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == READER]
    assert m["moves"] == "time_to_solution_s" and m["source"] == "device_trace"
