"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Sizes are CPU-scaled; the
full-scale (shape x mesh) numbers come from the dry-run/roofline pipeline
(see repro.launch.dryrun + benchmarks/roofline_report.py).
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from benchmarks.common import Report, TracedReport, repo_root_default  # noqa: E402
from benchmarks.trajectory import append_history  # noqa: E402


def main() -> None:
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    report = Report()
    out = repo_root_default()  # committed trajectory files live at the root
    history = out / "BENCH_history.jsonl"  # append-only perf trajectory
    print("name,us_per_call,derived", flush=True)

    # bench_solver and bench_batched track the cross-PR perf trajectory:
    # their rows also land in machine-readable BENCH_*.json files and the
    # append-only BENCH_history.jsonl that feeds the regression gate
    # (benchmarks/check_regression.py).
    from benchmarks import bench_solver  # noqa: E402

    solver_report = TracedReport("solver")
    bench_solver.run(solver_report)
    append_history(solver_report.write_json(out / "BENCH_solver.json"),
                   history)
    jax.clear_caches()

    from benchmarks import bench_batched  # noqa: E402

    batched_report = TracedReport("batched")
    bench_batched.run(batched_report)
    append_history(batched_report.write_json(out / "BENCH_batched.json"),
                   history)
    jax.clear_caches()

    from benchmarks import bench_serve  # noqa: E402

    serve_report = Report("serve")
    bench_serve.run(serve_report)
    append_history(serve_report.write_json(out / "BENCH_serve.json"),
                   history)
    jax.clear_caches()

    from benchmarks import bench_reorder  # noqa: E402

    bench_reorder.run(report)

    from benchmarks import bench_sparse_suite  # noqa: E402

    bench_sparse_suite.run(report)
    bench_sparse_suite.profile_stages(report)
    jax.clear_caches()

    from benchmarks import bench_kernels  # noqa: E402

    bench_kernels.run(report)


if __name__ == "__main__":
    main()
