"""The time-stepping loop: its refresh schedule, its check, and the readers
of its metrics on a hand-made record."""

import json

import pytest

from chipbench import control, harness, program_trace, tracing, work
from chipbench.loops import timestep
from chipbench.tests.conftest import CPU, ROOT
from chipbench.tracing import Event

NS = 1e-9
# the fleet cut to a size the CPU runs in seconds: 8 systems, 2 refreshed a step
TINY = {"n": 256, "k": 4, "p": 4, "systems": 8, "max_batch": 8, "fac_cache": 32}
TINY_TRAFFIC = {"refresh": 2, "jacobian_pool": 16, "rhs_pool": 4, "check_steps": 4}


def tiny_cell():
    cell = harness.load_cell("fleet_16k.timestep")
    cell.config.update(TINY)
    cell.traffic.update(TINY_TRAFFIC)
    return cell


def run_tiny(seed: int, seconds: float = 1.0) -> dict:
    from chipbench.run import run_cell

    return run_cell(tiny_cell(), seed, seconds, False, CPU)


def test_refresh_schedule_covers_the_fleet_once_a_cycle():
    assert [list(timestep.refreshed(s, 64, 16)) for s in (0, 1, 4, 7)] == [
        list(range(0, 16)), list(range(16, 32)), list(range(0, 16)), list(range(48, 64))]
    cycle = [i for s in range(4) for i in timestep.refreshed(s, 64, 16)]
    assert sorted(cycle) == list(range(64))


def test_schedule_is_the_same_for_every_seed():
    a, b = run_tiny(seed=2**33 + 5), run_tiny(seed=12)
    n = min(len(a["steps"]), len(b["steps"]))
    assert n >= 2
    warm = a["traffic"]["warm_steps"]
    for s in range(n):
        assert a["steps"][s]["refreshed"] == b["steps"][s]["refreshed"] == list(
            timestep.refreshed(warm + s, 8, 2))
        assert a["steps"][s]["live"] == b["steps"][s]["live"]
    for rec in (a, b):
        # every step: 2 misses of 8, no escalation, and the check passes
        assert rec["correct"] is True and rec["failed"] == 0
        e = rec["engine"]
        assert e["factored_systems"] == 2 * len(rec["steps"]) == e["cache_misses"]
        assert e["cache_hits"] == 6 * len(rec["steps"]) and e["escalations"] == 0
        assert rec["compiles_in_window"] == 0
        assert rec["compared"]["max_residual"]["value"] < 1e-5
        assert harness.load_reader("cache_hit_pct.fleet")(rec) == 75.0


def _scale_first_answer(real):
    def solve(fac, b, record_history=False):
        res = real(fac, b, record_history=record_history)
        return res._replace(x=res.x.at[0].multiply(1.001))
    return solve


@pytest.mark.parametrize("plant", ["control", "answer"])
def test_check_fails_the_planted_fault(plant, interpret, monkeypatch):
    """The control (every matmul one precision step below the configuration's)
    and an altered answer of the batched solve must both come out not correct."""
    from repro.core import batched

    if plant == "answer":
        monkeypatch.setattr(batched, "_solve_batch", _scale_first_answer(batched._solve_batch))
        rec = run_tiny(seed=11)
    else:
        with control.PLANTS[plant](tiny_cell().config):
            rec = run_tiny(seed=11)
    assert rec["correct"] is False and rec["failed"] > 0
    c = rec["compared"]["max_residual"]
    assert c["value"] > c["limit"]


def host(name, s, e):
    return Event("/host:CPU", "0:python3", name, float(s), float(e - s))


def op(name, s, e):
    return Event("/device:TPU:0", "XLA Ops", name, float(s), float(e - s))


# Two steps of 50 ns: prep 2, factor 16 (kernel 10 inside), stack 4, solve 25.
HAND = [host("bench.window", 0, 100)] + [
    ev for t in (0, 50) for ev in (
        host("bench.step", t, t + 50),
        host("sap.engine.prep", t + 1, t + 3),
        host("sap.engine.factor", t + 3, t + 19),
        host("sap.engine.stack", t + 19, t + 23),
        host("sap.engine.solve", t + 23, t + 48),
        op("%sap_fused_factor_spike.7 = f32[1] custom-call()", t + 5, t + 15),
        op("%while.3 = f32[1] while()", t + 25, t + 47))]


def hand_record() -> dict:
    cfg = json.loads((ROOT / "chipbench" / "configs" / "sap_fleet_16k.json").read_text())
    return {"program": program_trace.reduce(HAND), "trace": tracing.reduce(HAND),
            "steps": [{"iterations": 0.25}, {"iterations": 0.5}], "compiles_in_window": 0,
            "engine": {"steps": 2, "cache_hits": 96, "cache_misses": 32,
                       "factored_systems": 32, "krylov_lane_max_total": 1.5},
            "config": cfg, "device": {"kind": "TPU v5 lite"}}


def test_readers_read_a_hand_made_record():
    rec = hand_record()
    read = {m: harness.load_reader(m)(rec) for m in (
        "host_prep_s.fleet", "factor_s.fleet", "stack_s.fleet", "krylov_s.fleet",
        "krylov_iters.fleet", "krylov_lane_max.fleet", "cache_hit_pct.fleet",
        "compiles_in_window.fleet", "device_idle_pct.fleet", "fused_kernel_s.fleet",
        "factor_roofline.fleet")}
    one = work.least_time(work.factor_stage(16384, 16, 16, "C"), work.peaks("TPU v5 lite"))[0]
    assert read == {
        "host_prep_s.fleet": pytest.approx(2 * NS), "factor_s.fleet": pytest.approx(16 * NS),
        "stack_s.fleet": pytest.approx(4 * NS), "krylov_s.fleet": pytest.approx(25 * NS),
        "krylov_iters.fleet": 0.375, "krylov_lane_max.fleet": 0.75,
        "cache_hit_pct.fleet": 75.0, "compiles_in_window.fleet": 0,
        "device_idle_pct.fleet": pytest.approx(100 * (1 - 64 / 100)),
        "fused_kernel_s.fleet": pytest.approx(10 * NS),
        # 32 systems' least time over the 20 ns of kernel inside the factor spans
        "factor_roofline.fleet": pytest.approx(100 * 32 * one / (20 * NS)),
    }


@pytest.mark.parametrize("missing", ["program", "engine", "steps"])
def test_readers_find_nothing_where_the_record_lacks_it(missing):
    """The parent's engine has no ``engine.*`` spans nor lane counter, and an
    untraced run has no program reduction: those readers read None."""
    rec = {k: v for k, v in hand_record().items() if k != missing}
    lacking = {"program": ["host_prep_s.fleet", "factor_s.fleet", "stack_s.fleet",
                           "krylov_s.fleet", "fused_kernel_s.fleet", "factor_roofline.fleet"],
               "engine": ["krylov_lane_max.fleet", "cache_hit_pct.fleet",
                          "factor_roofline.fleet"],
               "steps": ["host_prep_s.fleet", "krylov_iters.fleet", "fused_kernel_s.fleet"]}
    for m in lacking[missing]:
        assert harness.load_reader(m)(rec) is None, m
    old = {k: v for k, v in hand_record()["engine"].items() if k != "krylov_lane_max_total"}
    assert harness.load_reader("krylov_lane_max.fleet")({"engine": old}) is None
