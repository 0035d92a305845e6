"""Run one cell of the chip benchmark once, on the machine it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic come from ``BENCHMARK.json``
and the files it names.  Set-up (inputs made from the seed on the device,
warm-up of every shape the traffic uses) is timed as ``setup_s``; then the
traffic runs for ``--seconds``; then the answers are checked against the
float64 reference (``reference.py``), and the last stdout line is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end
with ``--trace 0``, per-layer with ``--trace 1``), ``device`` and, last,
``compared``: each number the check compared, beside its limit.

There is no CPU fallback: without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.  JAX's persistent
compilation cache lives in ``.jax_cache/`` of this checkout (or where
``JAX_COMPILATION_CACHE_DIR`` says), so only a cell's first run compiles.
"""

from __future__ import annotations

import argparse
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


class Window:
    """The measured window: a ``bench.window`` span, compiles counted over
    it, and with ``trace`` the profiler running over it."""

    def __init__(self, counter, trace: bool):
        self.counter = counter
        self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
        self.memory_peak_bytes = None

    def __enter__(self):
        import jax

        from chipbench.tracing import annotate

        if self.trace_dir:
            # host spans and device operations; no trace of every Python call
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.c0 = self.counter.snapshot()
        self._span = annotate("window")
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        self.t1 = time.perf_counter()
        self._span.__exit__(*exc)
        self.c1 = self.counter.snapshot()
        if self.trace_dir:
            jax.profiler.stop_trace()
        return False

    def close_device(self):
        """Read the device's peak memory, before the check runs."""
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        self.memory_peak_bytes = stats.get("peak_bytes_in_use")

    def reduce_trace(self):
        from chipbench import tracing

        try:
            return tracing.reduce(tracing.load_events(tracing.find_xplane(self.trace_dir)))
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             device: dict) -> dict:
    """Set up, run the window and check it; returns the run's record."""
    counter = harness.CompileCounter()
    win = Window(counter, trace)
    loop = importlib.import_module(f"chipbench.loops.{cell.traffic['loop']}")
    setup0 = counter.snapshot()
    rec = loop.run(cell.config, cell.traffic, seed, seconds, win)
    rec["setup_compiles"] = {k: win.c0[k] - setup0[k] for k in setup0}
    rec["compiles_in_window"] = win.c1["compiles"] - win.c0["compiles"]
    rec["device"] = {**device, "memory_peak_bytes": win.memory_peak_bytes}
    rec["trace"] = win.reduce_trace() if trace else None
    rec["config"], rec["traffic"] = cell.config, cell.traffic
    for c in rec["compared"].values():
        c["value"] = harness.finite(float(c["value"]))
    return rec


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = harness.load_cell(args.workload)
    try:
        device = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 3

    import jax

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program, also the small ones, so a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"chipbench: {args.workload} seed {args.seed} on {device['count']} x "
          f"{device['kind']}, compile cache {cache_dir}", file=sys.stderr, flush=True)

    rec = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    line = harness.result_line(cell, rec, bool(args.trace))
    print(f"chipbench: set-up compiles {rec['setup_compiles']}, "
          f"compiles in window {rec['compiles_in_window']}", file=sys.stderr)
    harness.print_compared(rec["compared"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
