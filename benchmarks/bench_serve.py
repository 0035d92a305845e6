"""Serving soak benchmark: async service vs sequential engine loop.

Drives the same mixed-bucket fleet workload -- several distinct
Jacobians across two compiled buckets, re-solved over many "time steps"
with fresh right-hand sides, arriving from concurrent clients -- through
two serving disciplines:

  * ``serve/sequential`` -- the synchronous pattern the repo had before
    the async service: each arrival is ``submit()`` + ``run_until_
    drained()`` before the client proceeds (no batching across arrivals,
    no host/device overlap).
  * ``serve/async``      -- :class:`repro.serve.service.AsyncSolverService`:
    clients submit from threads and block on futures; the background
    drain thread batches concurrent arrivals per bucket and overlaps
    host-side fingerprinting/bucketing with in-flight device solves.

The acceptance row reports the solves/sec ratio (target >= 1.5x), the
deadline-miss count at the default load (target 0), and dumps the full
metrics snapshot -- queue-depth / time-in-queue / batch-occupancy
histograms, hit rate -- into the ``BENCH_serve.json`` trajectory file.

Run standalone: ``python -m benchmarks.bench_serve [--smoke] [--out D]``.
"""

from __future__ import annotations

import argparse
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import SaPOptions  # noqa: E402
from repro.core.banded import random_banded  # noqa: E402
from repro.serve import AsyncSolverService, SolverEngine  # noqa: E402

from benchmarks.common import Report, repo_root_default  # noqa: E402


def _workload(smoke: bool):
    """Mixed-bucket fleet: distinct Jacobians x repeated time steps."""
    if smoke:
        shapes, steps, clients = [(256, 4), (300, 4), (512, 8)], 4, 4
    else:
        shapes, steps, clients = [(1024, 8), (1500, 8), (2048, 16)], 8, 8
    mats = [
        np.float32(random_banded(n, k, d=1.1, seed=7 * i + j))
        for i, (n, k) in enumerate(shapes)
        for j in range(2)  # two distinct Jacobians per shape
    ]
    rng = np.random.default_rng(0)
    reqs = []
    for s in range(steps):
        for band in mats:
            reqs.append((band, rng.normal(size=band.shape[0])
                         .astype(np.float32)))
    return reqs, clients


def _opts():
    return SaPOptions(p=4, variant="C", tol=1e-6, maxiter=300)


def _run_sequential(reqs):
    eng = SolverEngine(_opts(), max_batch=32, cache_size=64)
    t0 = time.perf_counter()
    done = []
    for band, b in reqs:  # one arrival at a time: submit, then drain
        eng.submit_system(band, b)
        done.extend(eng.run_until_drained())
    wall = time.perf_counter() - t0
    assert all(r.result.converged for r in done)
    true_res = max(r.result.true_resnorm for r in done)
    return wall, len(done), eng, true_res


def _run_async(reqs, clients, deadline_s=120.0):
    svc = AsyncSolverService(
        _opts(), max_batch=32, cache_size=64, queue_cap=256
    )
    chunks = [reqs[i::clients] for i in range(clients)]
    futs_by_client = [[] for _ in range(clients)]

    def client(cid):
        for band, b in chunks[cid]:
            futs_by_client[cid].append(
                svc.submit(band, b, deadline_s=deadline_s, timeout=300)
            )

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=client, args=(c,)) for c in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    outs = [f.result(timeout=600) for futs in futs_by_client for f in futs]
    wall = time.perf_counter() - t0
    assert all(o.converged for o in outs)
    true_res = max(o.true_resnorm for o in outs)
    svc.close()
    return wall, len(outs), svc, true_res


def run(report: Report, smoke: bool = False) -> dict:
    reqs, clients = _workload(smoke)

    # warm the jit caches for every bucket once, outside both timings --
    # the comparison is serving discipline, not compile time
    warm = SolverEngine(_opts(), max_batch=32, cache_size=64)
    for band, b in reqs:
        warm.submit_system(band, b)
    warm.run_until_drained()

    tol = _opts().tol
    wall_seq, n_seq, eng, tr_seq = _run_sequential(reqs)
    sps_seq = n_seq / wall_seq
    report.add(
        "serve/sequential",
        wall_seq * 1e6 / n_seq,
        f"solved={n_seq};sys_per_s={sps_seq:.1f};"
        f"hit_rate={eng.cache_hit_rate:.2f};steps={eng.stats['steps']};"
        f"conv=True;true_res={tr_seq:.3e};tol={tol:g}",
    )

    wall_async, n_async, svc, tr_async = _run_async(reqs, clients)
    snap = svc.snapshot()
    sps_async = n_async / wall_async
    misses = int(snap["counters"].get("deadline_misses", 0))
    misconv = int(snap["counters"].get("misconverged_total", 0))
    occ = snap["histograms"]["batch_occupancy"]
    report.add(
        "serve/async",
        wall_async * 1e6 / n_async,
        f"solved={n_async};sys_per_s={sps_async:.1f};"
        f"speedup={sps_async / sps_seq:.2f}x;"
        f"deadline_misses={misses};clients={clients};"
        f"hit_rate={snap['derived']['cache_hit_rate']:.2f};"
        f"occupancy_mean={occ['mean']:.2f};"
        f"queue_p90={snap['histograms']['queue_depth']['p90']:.0f};"
        f"conv=True;true_res={tr_async:.3e};tol={tol:g};"
        f"misconverged={misconv}",
    )
    return {
        "smoke": smoke,
        "clients": clients,
        "requests": len(reqs),
        "speedup": round(sps_async / sps_seq, 3),
        "deadline_misses": misses,
        "misconverged_total": misconv,
        "async_metrics": snap,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few steps (CI smoke job)")
    ap.add_argument("--out", default=str(repo_root_default()),
                    help="directory for BENCH_serve.json "
                         "(default: the repo root)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    report = Report("serve")
    print("name,us_per_call,derived", flush=True)
    meta = run(report, smoke=args.smoke)
    report.write_json(Path(args.out) / "BENCH_serve.json", meta=meta)
    if meta["speedup"] < 1.5:
        print(f"WARNING: async speedup {meta['speedup']}x below 1.5x target",
              flush=True)


if __name__ == "__main__":
    main()
