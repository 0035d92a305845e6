"""Implicit time stepping: one closed-loop client drives a fleet of systems
through ``SolverEngine`` and waits for all of a step's answers.

Each of ``systems`` systems solves one linear system a step.  At step s the
``refresh`` systems ``refresh * (s mod c) ..``, c = systems / refresh, get a
new Jacobian: the next entry of a pool of ``jacobian_pool`` device-resident
seeded bands, taken in turn.  The schedule does not depend on the seed,
which chooses only the values in the pools.  Every system then submits one
request with a fresh right-hand side (``rhs_pool`` sets, used in turn),
keyed by the caller as ``f"{system}.{version}"`` so that the engine never
hashes a band, and the engine drains the step as one batch: ``refresh``
cache misses, the rest hits.

Set-up fills the fleet (one batch that factors every system) and runs
``warm_steps`` steps, a whole refresh cycle, so every shape of the window is
compiled.  The check takes ``check_steps`` steps drawn from the seed: each
answer's float64 residual against the band that step used.  A run with an
escalation in the window is not correct either: escalation re-solves, which
would make a step's work depend on the data.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from chipbench import generate, reference
from chipbench.loops.closed import CHECK, _opts, _pool, _split_pool
from chipbench.tracing import annotate

# engine counters the window reads, by difference (a counter the engine
# lacks reads as absent)
COUNTERS = ("steps", "cache_hits", "cache_misses", "factored_systems", "escalations",
            "krylov_lane_max_total")


def refreshed(step: int, systems: int, refresh: int) -> range:
    """The systems that get a new Jacobian at ``step``."""
    first = refresh * (step % (systems // refresh))
    return range(first, first + refresh)


def per_step(rec: dict, span: str, key: str = "seconds"):
    """``key`` of the program's span ``sap.<span>`` over the traced window,
    per step; None where the record has no such span."""
    spans = (rec.get("program") or {}).get("spans", {})
    if not rec.get("steps") or "sap." + span not in spans:
        return None
    return spans["sap." + span][key] / len(rec["steps"])


def _counters(engine) -> dict:
    snap = engine.stats_snapshot()
    return {c: snap[c] for c in COUNTERS if c in snap}


def _program(win):
    """The program's spans over the traced window (``program_trace``)."""
    from chipbench import program_trace, tracing

    program = program_trace.reduce(program_trace.load(tracing.find_xplane(win.trace_dir)))
    print(program_trace.table(program), file=sys.stderr, flush=True)
    return program


def run(cfg: dict, traffic: dict, seed: int, seconds: float, window) -> dict:
    """Set up, run the window, check a sample; the run's record."""
    import jax

    from repro.serve import SolveRequest, SolverEngine

    t_setup = time.perf_counter()
    n, k, systems = cfg["n"], cfg["k"], cfg["systems"]
    refresh, pool, sets = traffic["refresh"], traffic["jacobian_pool"], traffic["rhs_pool"]
    if systems % refresh or pool < systems + refresh:
        raise ValueError(f"refresh {refresh} must divide systems {systems}, and the pool "
                         f"{pool} hold every live Jacobian and the next refresh")
    engine = SolverEngine(_opts(cfg), max_batch=cfg["max_batch"],
                          cache_size=cfg["fac_cache"], rounding=cfg["rounding"])
    with annotate("generate"):
        bands = _pool(generate.stream(seed, generate.BANDS), pool,
                      lambda key, c: generate.bands(key, c, n, k, cfg["d"]))
        rhs = [_split_pool(s) for s in _pool(generate.stream(seed, generate.RHS), sets,
                                             lambda key, c: generate.normal(key, (c, systems, n)))]
        jax.block_until_ready((bands, rhs))

    # system i holds pool entry live[i], its version[i]-th Jacobian
    live, version = list(range(systems)), [0] * systems
    taken = systems

    def step(s: int) -> dict:
        nonlocal taken
        fresh = refreshed(s, systems, refresh) if s >= 0 else range(0)
        for i in fresh:
            live[i], version[i] = taken % pool, version[i] + 1
            taken += 1
        j = s % sets
        for i in range(systems):
            engine.submit(SolveRequest(rid=i, band=bands[live[i]], b=rhs[j][i],
                                       fingerprint=f"{i}.{version[i]}"))
        done = sorted(engine.run_until_drained(), key=lambda r: r.rid)
        return {"t_end": time.perf_counter(), "refreshed": list(fresh), "live": list(live),
                "rhs": j, "iterations": float(np.mean([r.result.iterations for r in done])),
                "unconverged": [r.rid for r in done if not r.result.converged],
                "x": [r.result.x for r in done]}

    with annotate("warmup"):
        step(-1)  # the fleet's first Jacobians: every system factors
        for s in range(traffic["warm_steps"]):
            step(s)
    setup_s = time.perf_counter() - t_setup

    steps = []
    with window as win:
        c0 = _counters(engine)
        t_end = time.perf_counter() + seconds
        s = traffic["warm_steps"]
        while time.perf_counter() < t_end:
            with annotate("step"):
                steps.append(step(s))
            s += 1
        c1 = _counters(engine)
    window_s = steps[-1]["t_end"] - win.t0
    counters = {c: c1[c] - c0[c] for c in c1}
    rec = {"setup_s": setup_s, "window_s": window_s, "steps": steps,
           "attempted": len(steps) * systems, "engine": counters}
    win.close_device()
    if win.trace_dir:
        rec["program"] = _program(win)

    # the check: a sample of steps drawn from the seed, in float64 on the host
    rng = np.random.default_rng([seed, CHECK])
    sample = sorted(rng.choice(len(steps), min(traffic["check_steps"], len(steps)),
                               replace=False).tolist())
    limit = cfg["check"]["max_residual"]
    host_bands: dict = {}
    worst, failed = 0.0, set()
    with annotate("check"):
        for s in sample:
            st = steps[s]
            for i in range(systems):
                if st["live"][i] not in host_bands:
                    host_bands[st["live"][i]] = np.asarray(bands[st["live"][i]])
                r = reference.residual(host_bands[st["live"][i]], st["x"][i],
                                       np.asarray(rhs[st["rhs"]][i]))[0]
                r = np.inf if np.isnan(r) else float(r)
                worst = max(worst, r)
                if not r <= limit:
                    failed.add((s, i))
    failed |= {(s, i) for s, st in enumerate(steps) for i in st["unconverged"]}
    for st in steps:
        del st["x"]
    escalations = counters.get("escalations", 0)
    rec.update(failed=len(failed), correct=not failed and escalations == 0,
               compared={"max_residual": {"value": worst, "limit": limit},
                         "escalations": {"value": escalations, "limit": 0}})
    return rec
