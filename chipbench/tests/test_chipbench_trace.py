"""The reduction from a profiler trace to busy time, idle share, device
time by operation and by host span, and idle gaps by host span."""

from pathlib import Path

import pytest

from chipbench import tracing
from chipbench.tracing import Event

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json"
TPU0, TPU1, OPS = "/device:TPU:0", "/device:TPU:1", "XLA Ops"


def host(name, s, e):
    return Event("/host:CPU", "python", name, float(s), float(e - s))


def op(name, s, e, plane=TPU0):
    return Event(plane, OPS, name, float(s), float(e - s))


HAND = [
    host("bench.window", 0, 100),
    host("bench.factor", 10, 50),
    host("bench.solve", 55, 95),
    host("not.ours", 0, 100),
    op("fusion.1", 12, 20), op("fusion.2", 15, 30), op("kernel", 40, 48),
    op("fusion.3", 60, 90),
]
NS = 1e-9


def test_busy_union_and_idle_share():
    r = tracing.reduce(HAND)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(100 * NS)
    assert r["busy_s"] == pytest.approx(56 * NS)  # [12, 30] + [40, 48] + [60, 90]


def test_device_time_by_operation_and_by_span():
    r = tracing.reduce(HAND)
    assert r["device_ops"] == [[n, pytest.approx(v * NS)] for n, v in
                               [("?:fusion.3", 30), ("?:fusion.2", 15), ("?:fusion.1", 8),
                                ("?:kernel", 8)]]
    assert r["device_s_in_span"]["factor"] == pytest.approx(26 * NS)
    assert r["device_s_in_span"]["solve"] == pytest.approx(30 * NS)
    assert r["span_s"] == {"factor": pytest.approx(40 * NS), "solve": pytest.approx(40 * NS)}
    assert r["span_count"] == {"factor": 1, "solve": 1}


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    # gaps [0,12] and [30,40] lie in factor; [48,60] is more solve than
    # factor; [90,100] in solve
    r = tracing.reduce(HAND)
    assert dict((k, v) for k, v in r["idle_gaps"]) == {
        "factor": pytest.approx(22 * NS), "solve": pytest.approx(22 * NS)}
    lone = tracing.reduce([host("bench.window", 0, 10), op("a", 2, 4)])
    assert lone["idle_gaps"] == [["no span", pytest.approx(8 * NS)]]


def test_operations_are_named_by_the_program_that_holds_them():
    ops = [op("%fusion.1 = f32[8] fusion(x)", 12, 20), op("%fusion.1 = f32[8] fusion(y)", 60, 70)]
    mods = [Event(TPU0, "XLA Modules", "jit_factor(123)", 10.0, 40.0),
            Event(TPU0, "XLA Modules", "jit_solve(9)", 55.0, 40.0)]
    r = tracing.reduce([host("bench.window", 0, 100)] + ops + mods)
    assert r["device_ops"] == [["jit_solve:fusion.1", pytest.approx(10 * NS)],
                               ["jit_factor:fusion.1", pytest.approx(8 * NS)]]
    # a program is not itself an operation: busy counts the ops alone
    assert r["busy_s"] == pytest.approx(18 * NS)


def test_busy_is_averaged_over_chips_and_clipped_to_the_window():
    r = tracing.reduce(HAND + [op("x", -20, 50, TPU1)])
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((56 + 50) / 2 * NS)


def test_a_trace_without_device_operations_raises():
    with pytest.raises(ValueError):
        tracing.reduce([host("bench.window", 0, 10)])


def _sweep_busy(events, lo, hi):
    """Busy time by a sweep over sorted end points: an independent count."""
    points = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            points += [(s, 1), (t, -1)]
    busy, depth, last = 0.0, 0, None
    for x, d in sorted(points):
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    return busy


def test_recorded_chip_trace():
    events = tracing.read_events(str(DATA))
    window = [e for e in events if e.name == "bench.window"][0]
    lo, hi = window.start_ns, window.end_ns
    ops = [e for e in events if tracing.is_device_plane(e.plane) and e.line == tracing.OPS]
    r = tracing.reduce(events)
    assert r["busy_s"] == pytest.approx(_sweep_busy(ops, lo, hi) * NS, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    for kind in ("factor", "solve"):
        spans = [e for e in events if e.name == "bench." + kind]
        inside = sum(_sweep_busy(ops, max(s.start_ns, lo), min(s.end_ns, hi)) for s in spans)
        assert r["device_s_in_span"][kind] == pytest.approx(inside * NS, rel=1e-9)
        assert r["span_count"][kind] == len(spans)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
