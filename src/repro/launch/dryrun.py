import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
    + " "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry run of the distributed solver.

Lowers + compiles one (solver shape x mesh) cell on placeholder host
devices and extracts memory analysis, cost analysis and the collective
schedule for the roofline report.  THE XLA_FLAGS LINE ABOVE MUST STAY
FIRST: jax locks the device count at first initialization.

Usage:
  python -m repro.launch.dryrun --shape dense_200k --multi-pod
  python -m repro.launch.dryrun --list
Options: --multi-pod, --out out.json, --save-hlo hlo.txt,
         --variant {C,D,E} (E = exact reduced chain via distributed cyclic
         reduction), --p-per-device, --precond-dtype {float32,bfloat16}.
``--arch sap-solver`` is accepted (the only arch) so older command lines
still work.
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.sap_solver import SOLVER_SHAPES
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze


def _mem_dict(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    out = {}
    for key in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, key, None)
        if v is not None:
            out[key] = int(v)
    out["total_per_device"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0)
    )
    return out


def _cost_dict(compiled):
    try:
        cost = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return {k: float(v) for k, v in cost.items() if isinstance(v, (int, float))}


# ---------------------------------------------------------------------------
# Solver cells (the paper's own workload)
# ---------------------------------------------------------------------------


def lower_solver_cell(shape_name: str, multi_pod: bool, args):
    from repro.core.distributed import build_dist_sap, solve_step_fn

    sshape = SOLVER_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    variant = args.variant
    pdt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[args.precond_dtype]
    dsap = build_dist_sap(mesh, sshape.n, sshape.k, variant=variant,
                          p_per_device=args.p_per_device, precond_dtype=pdt)
    k, m = dsap.k, dsap.m
    p_total = chips * args.p_per_device
    n_pad = dsap.n_pad
    axes = tuple(mesh.axis_names)

    sd = jax.ShapeDtypeStruct
    f32 = jnp.float32
    ins = (
        sd((n_pad, 2 * k + 1), f32),  # band
        sd((n_pad,), f32),  # b
        sd((p_total, m, k, k), pdt),  # d
        sd((p_total, m, k, k), pdt),  # e
        sd((p_total, m, k, k), pdt),  # f
        sd((p_total, k, k), pdt),  # b_next
        sd((p_total, k, k), pdt),  # c_prev
    )
    shardings = (
        NamedSharding(mesh, P(axes, None)),
        NamedSharding(mesh, P(axes)),
        NamedSharding(mesh, P(axes, None, None, None)),
        NamedSharding(mesh, P(axes, None, None, None)),
        NamedSharding(mesh, P(axes, None, None, None)),
        NamedSharding(mesh, P(axes, None, None)),
        NamedSharding(mesh, P(axes, None, None)),
    )
    step = solve_step_fn(dsap, tol=1e-8, maxiter=100)
    with mesh:
        jitted = jax.jit(step, in_shardings=shardings, out_shardings=None)
        lowered = jitted.lower(*ins)
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    hlo = compiled.as_text()
    cost = _cost_dict(compiled)
    # useful flops: block factorization + one preconditioned iteration
    factor_flops = p_total * m * 8 * k**3
    iter_flops = 4 * (2 * n_pad * (2 * k + 1) + p_total * m * 8 * k * k)
    roof = analyze(cost, hlo, chips, float(factor_flops + iter_flops))
    row = {
        "arch": "sap-solver",
        "shape": shape_name,
        "kind": f"solve-{variant}",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "compile_s": round(compile_s, 1),
        "memory": _mem_dict(compiled),
        "cost": cost,
        "roofline": roof.to_dict(),
        "n": sshape.n,
        "k": k,
        "p_total": p_total,
        "variant": variant,
        "p_per_device": args.p_per_device,
        "precond_dtype": args.precond_dtype,
    }
    if args.save_hlo:
        with open(args.save_hlo, "w") as f:
            f.write(hlo)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="sap-solver", choices=["sap-solver"])
    ap.add_argument("--shape", default=None, choices=list(SOLVER_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--variant", default="C", choices=["C", "D", "E"])
    ap.add_argument("--p-per-device", type=int, default=1)
    ap.add_argument("--precond-dtype", default="float32")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for s in SOLVER_SHAPES:
            print(f"sap-solver x {s}")
        return
    if args.shape is None:
        ap.error("--shape is required (see --list)")

    row = lower_solver_cell(args.shape, args.multi_pod, args)

    js = json.dumps(row, indent=2, default=str)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js)
    print(js)
    mem = row["memory"].get("total_per_device", 0)
    print(
        f"\n== {row['arch']} x {row['shape']} on {row['mesh']}: "
        f"{mem/2**30:.2f} GiB/device, compile {row['compile_s']}s ==",
        file=sys.stderr,
    )


if __name__ == "__main__":
    main()
