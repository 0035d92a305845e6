"""Batched fleet-solve benchmarks: vmapped many-system throughput.

Measures the new batched subsystem (``repro.core.batched`` +
``repro.serve.solver_engine``) against the naive python loop the paper's
target workload would otherwise run -- one plan/factor/solve round trip
per system -- across batch sizes {1, 8, 32, 128}:

  * ``fleet/loop_S``    -- python loop: per-system ``factor(plan_banded)``
                           + ``solve`` (the expensive stages re-run S times)
  * ``fleet/batched_S`` -- one ``batch_factor`` (vmapped device stages) +
                           one ``solve_batch`` over the stacked fleet
  * ``engine/*``        -- the serving path: bucketed heterogeneous fleet
                           with repeated matrices through ``SolverEngine``
                           (cache-hit rate + systems/s)

Run standalone (``python -m benchmarks.bench_batched [--smoke] [--out D]``)
to emit the machine-readable ``BENCH_batched.json`` trajectory file.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    SaPOptions,
    batch_factor,
    batch_plan,
    factor,
    plan_banded,
)
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.banded import band_matvec, random_banded  # noqa: E402
from repro.obs.cost import solver_stage_costs  # noqa: E402
from repro.serve import SolverEngine  # noqa: E402

from benchmarks.common import (  # noqa: E402
    Report,
    TracedReport,
    repo_root_default,
    stage_fractions,
    timeit,
)


def _fleet(s, n, k, d=1.0, seed=0):
    """S independent banded systems (same shape; distinct entries) + RHS."""
    bands, bs, xs = [], [], []
    rng = np.random.default_rng(seed)
    for i in range(s):
        band = jnp.asarray(random_banded(n, k, d=d, seed=seed + i), jnp.float32)
        x = rng.normal(size=n)
        bands.append(band)
        xs.append(x)
        bs.append(band_matvec(band, jnp.asarray(x, jnp.float32)))
    return bands, jnp.stack(bs), np.stack(xs)


def _fleet_cost(tr, bpl, res, opts) -> dict | None:
    """Per-stage cost records for one fleet/batched row.

    Roofline predictions come from the bucket's AOT cost analysis
    (:func:`repro.obs.cost.solver_stage_costs`); measured seconds come
    from the traced pass's factor.batch / krylov spans, with the krylov
    prediction rescaled from the lowered maxiter loop to the sweeps the
    batch actually ran (a lockstep vmapped solve runs max(iterations)
    sweeps for everyone).
    """
    try:
        costs = solver_stage_costs(
            (bpl.n, bpl.k, opts.p), s=bpl.s, opts=opts
        )
    except Exception:  # cost analysis must never sink the benchmark
        return None
    factor_s = sum(sp.duration_s for sp in tr.find("factor.batch"))
    krylov_s = sum(sp.duration_s for sp in tr.find("krylov"))
    sweeps = max(1, int(np.ceil(float(np.asarray(res.iterations).max()))))
    out = {
        "factor": costs["factor"].to_dict(measured_s=factor_s or None),
        "krylov": costs["krylov"].per_iteration().scale(sweeps)
        .to_dict(measured_s=krylov_s or None),
    }
    for sub in ("btf", "bts", "bcr"):  # kernel-level reference records
        if sub in costs:
            out[sub] = costs[sub].to_dict()
    return out


def bench_fleet(report: Report, smoke: bool = False):
    """Batched solve_batch vs the python loop of per-system factor+solve."""
    n, k, p = (512, 8, 4) if smoke else (2048, 8, 8)
    batches = (1, 8) if smoke else (1, 8, 32, 128)
    opts = SaPOptions(p=p, variant="C", tol=1e-6, maxiter=200)
    for s in batches:
        jax.clear_caches()
        bands, bmat, xs = _fleet(s, n, k)

        def loop_all():
            out = []
            for i in range(s):
                fac = factor(plan_banded(bands[i], opts))
                out.append(fac.solve(bmat[i]).x)
            return out

        us_loop = timeit(loop_all, warmup=1, iters=1)

        def batched_all():
            bfac = batch_factor(batch_plan(bands, opts))
            return bfac.solve_batch(bmat).x

        us_batched = timeit(batched_all, warmup=1, iters=3)

        # One traced pass (post-timing, so tracer overhead never pollutes
        # the us_per_call figures) to attribute wall time to stages.
        with report.tracing() as tr:
            bpl = batch_plan(bands, opts)
            bfac = batch_factor(bpl)
            res = bfac.solve_batch(bmat)
            jax.block_until_ready(res.x)
        err = float(np.abs(np.asarray(res.x)[:, :n] - xs).max())
        true_res = float(np.asarray(res.true_resnorm).max())
        report.add(f"fleet/loop_S={s}", us_loop, "replan+refactor per system")
        report.add(
            f"fleet/batched_S={s}",
            us_batched,
            f"speedup={us_loop / us_batched:.1f}x;"
            f"per_system_us={us_batched / s:.1f};maxerr={err:.1e};"
            f"conv={bool(np.asarray(res.converged).all())};"
            f"true_res={true_res:.3e};tol={opts.tol:g}",
            stages=stage_fractions(tr),
            cost=_fleet_cost(tr, bpl, res, opts),
        )


def bench_fused(report: Report, smoke: bool = False):
    """Fused factor+spike megakernel vs the kernel-sequence baseline.

    Same bucket, same fleet, ``fused_factor="off"`` (btf -> UL-btf ->
    spike solves) vs ``"on"`` (one fused pass,
    :mod:`repro.kernels.fused_spike`).  Each row carries the cost
    observatory's factor-stage record for its path: the fused pass never
    materializes the UL factors or the whole spikes, so its factor-stage
    HBM bytes must come in *below* the sequence baseline -- that byte gap
    is the committed, machine-checkable form of the megakernel claim
    (visible even on the jnp path, where XLA's cost analysis counts the
    same skipped materializations).
    """
    n, k, p, s = (512, 8, 4, 8) if smoke else (2048, 8, 8, 32)
    bands, bmat, xs = _fleet(s, n, k)
    stage = {}
    for mode in ("off", "on"):
        opts = SaPOptions(p=p, variant="C", tol=1e-6, maxiter=200,
                          fused_factor=mode)
        jax.clear_caches()
        bpl = batch_plan(bands, opts)

        def factor_only():
            return batch_factor(bpl).fac.pc

        us = timeit(factor_only, warmup=1, iters=3)
        res = batch_factor(bpl).solve_batch(bmat)
        err = float(np.abs(np.asarray(res.x)[:, :n] - xs).max())
        try:
            cost = solver_stage_costs((bpl.n, bpl.k, p), s=s, opts=opts)
        except Exception:
            cost = None
        rec = cost["factor"] if cost else None
        stage[mode] = rec
        label = "fused" if mode == "on" else "sequence"
        extra = ""
        if mode == "on" and stage["off"] is not None and rec is not None:
            saved = stage["off"].hbm_bytes - rec.hbm_bytes
            extra = (f";hbm_bytes_saved={saved:.3e}"
                     f";bytes_ratio={rec.hbm_bytes / stage['off'].hbm_bytes:.4f}")
        report.add(
            f"fused/factor_{label}_S={s}",
            us,
            f"maxerr={err:.1e};"
            f"conv={bool(np.asarray(res.converged).all())};"
            f"true_res={float(np.asarray(res.true_resnorm).max()):.3e};"
            f"tol={opts.tol:g}"
            + (f";factor_hbm_bytes={rec.hbm_bytes:.4e}" if rec else "")
            + extra,
            cost={"factor": rec.to_dict()} if rec else None,
        )


def bench_engine(report: Report, smoke: bool = False):
    """Serving path: heterogeneous fleet, repeated matrices, LRU cache."""
    n0, k0, steps, distinct = (256, 4, 3, 2) if smoke else (1024, 8, 8, 4)
    opts = SaPOptions(p=4, variant="C", tol=1e-6, maxiter=200)
    eng = SolverEngine(opts, max_batch=32, cache_size=64,
                       cost_accounting=True)
    rng = np.random.default_rng(3)
    mats = [
        np.float32(random_banded(n0 + 37 * i, k0 + (i % 2), d=1.1, seed=i))
        for i in range(distinct)
    ]
    with report.tracing() as tr:
        t0 = time.perf_counter()
        for _ in range(steps):  # time-stepping: same matrices, fresh RHS
            for band in mats:
                b = rng.normal(size=band.shape[0]).astype(np.float32)
                eng.submit_system(band, b)
        done = eng.run_until_drained()
        wall = time.perf_counter() - t0
    conv = all(r.result.converged for r in done)
    true_res = max(r.result.true_resnorm for r in done)
    report.add(
        "engine/fleet",
        wall * 1e6 / max(len(done), 1),
        f"solved={len(done)};hit_rate={eng.cache_hit_rate:.2f};"
        f"factored={eng.stats['factored_systems']};"
        f"steps={eng.stats['steps']};sys_per_s={len(done) / wall:.1f};"
        f"conv={conv};true_res={true_res:.3e};tol={opts.tol:g};"
        f"misconverged={eng.stats['misconverged']}",
        stages=stage_fractions(tr),
        cost=_engine_cost(eng),
    )


def _engine_cost(eng: SolverEngine) -> dict | None:
    """Fold the engine's accumulated cost totals into per-stage records.

    Measured seconds are the engine's own stage accounting
    (factor_seconds_total / solve_seconds_total); predictions are the
    roofline totals the engine accrued per step (S=1 linear-scaling
    factor model, sweeps x batch krylov model).
    """
    totals = eng.cost_snapshot()
    if not totals:
        return None
    measured = {
        "factor": eng.stats["factor_seconds_total"],
        "krylov": eng.stats["solve_seconds_total"],
    }
    out = {}
    for stage, t in totals.items():
        rec = dict(t)
        m = measured.get(stage)
        if m:
            rec["measured_s"] = round(m, 6)
            rec["roofline_frac"] = round(t["roofline_s"] / m, 6)
        out[stage] = rec
    return out


def run(report: Report, smoke: bool = False):
    bench_fleet(report, smoke)
    bench_fused(report, smoke)
    bench_engine(report, smoke)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / small batches (CI smoke job)")
    ap.add_argument("--out", default=str(repo_root_default()),
                    help="directory for BENCH_batched.json "
                         "(default: the repo root)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    report = TracedReport("batched")
    print("name,us_per_call,derived", flush=True)
    run(report, smoke=args.smoke)
    report.write_json(
        Path(args.out) / "BENCH_batched.json", meta={"smoke": args.smoke}
    )


if __name__ == "__main__":
    main()
