"""The control and the planted faults of the check, and the readings of
the compared numbers over many seeds in one process.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--plant none|control|answer]

``--plant none`` reads sound runs (the lower reading of each compared
number).  ``control`` runs every matrix product of the solver one step
below the configuration's ``matmul_precision``: for float32 at
``highest``, XLA's ``high`` (three bf16 passes: hi*hi + hi*lo + lo*hi of
the operands' bf16 splits), written out so that it holds on every backend
and inside the Pallas kernels.  ``answer`` alters one answer where the
solver produces it.  Each must come out not correct.  Every seed runs its whole
cell (set-up, window, check) in this one process, with the profiler off;
one JSON line per seed.  The benchmark's own runs never plant anything.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def mm_high(a, b):
    """``a @ b`` at XLA's ``high`` precision: three bf16 passes."""
    import jax
    import jax.numpy as jnp

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

    dot = lambda x, y: jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)
    a1, a2 = split(a.astype(jnp.float32))
    b1, b2 = split(b.astype(jnp.float32))
    return (dot(a1, b1) + dot(a1, b2) + dot(a2, b1)).astype(jnp.result_type(a, b))


@contextlib.contextmanager
def _patched(targets):
    """Replace module attributes (``(module, name, value)``), then restore.

    JAX's caches of traced functions are cleared on the way in and out:
    a jitted function traced under one binding must not be reused under
    the other."""
    import jax

    saved = []
    try:
        for mod, name, value in targets:
            m = importlib.import_module(mod)
            saved.append((m, name, getattr(m, name)))
            setattr(m, name, value)
        jax.clear_caches()
        yield
    finally:
        for m, name, value in reversed(saved):
            setattr(m, name, value)
        jax.clear_caches()


# The configuration's matmul precision -> the product one step below it.
LOWER = {"highest": mm_high}


def plant_control(cfg: dict):
    """Every module of the solver that binds its one matrix product
    ``repro.core.block_lu.mm`` gets the product one step below the
    configuration's ``matmul_precision`` in its place."""
    import repro.core.block_lu
    import repro.kernels.ops  # noqa: F401

    lower = LOWER[cfg["matmul_precision"]]
    mm = repro.core.block_lu.mm
    users = [name for name, mod in sorted(sys.modules.items())
             if name.startswith("repro.") and getattr(mod, "mm", None) is mm]
    return _patched([(m, "mm", lower) for m in users])


def plant_answer(cfg: dict):
    """An answer altered where it is produced: the first answer of every
    ``solve_many`` (column 0) is scaled by 1 + 1e-3."""
    from repro.core import sap

    def many(fac, b, record_history=False):
        res = solve_many(fac, b, record_history=record_history)
        return res._replace(x=res.x.at[:, 0].multiply(1.001))

    solve_many = sap._solve_many
    return _patched([("repro.core.sap", "_solve_many", many)])


PLANTS = {"none": lambda cfg: contextlib.nullcontext(), "control": plant_control,
          "answer": plant_answer}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--plant", choices=sorted(PLANTS), default="none")
    args = parser.parse_args(argv)

    from chipbench import harness
    from chipbench.run import run_cell

    cell = harness.load_cell(args.workload)
    device = harness.require_chips(cell.chips)

    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with PLANTS[args.plant](cell.config):
        for seed in (int(s) for s in args.seeds.split(",")):
            rec = run_cell(cell, seed, args.seconds, False, device)
            line = harness.result_line(cell, rec, False)
            print(json.dumps({"seed": seed, "plant": args.plant, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
