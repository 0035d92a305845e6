"""Multi-device tests, run in subprocesses with 8 forced host devices
(the in-process suite must keep seeing exactly 1 device)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"


def _run(script: str, devices: int = 8, timeout: int = 900, x64: bool = False):
    env = {
        "PYTHONPATH": str(SRC),
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "JAX_PLATFORMS": "cpu",
        "PATH": "/usr/bin:/bin",
        "HOME": "/root",
    }
    if x64:
        env["JAX_ENABLE_X64"] = "1"
    return subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=timeout, env=env,
    )


DIST_SAP = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.banded import random_banded, band_to_dense
from repro.core.distributed import build_dist_sap, solve_step_fn
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((2, 4), ("data", "model"))
n, k = 600, 6
band = random_banded(n, k, d=1.0, seed=5)
A = np.asarray(band_to_dense(jnp.asarray(band)))
xstar = np.random.default_rng(0).normal(size=n)
b = A @ xstar
for variant in ("C", "D", "E"):
    dsap = build_dist_sap(mesh, n, k, variant=variant, p_per_device=2)
    band_p, b_p, parts = dsap.shard_band(band, b)
    step = solve_step_fn(dsap, tol=1e-6, maxiter=300)
    with mesh:
        res = jax.jit(step)(
            band_p.astype(jnp.float32), b_p.astype(jnp.float32),
            parts["d"], parts["e"], parts["f"], parts["b_next"], parts["c_prev"])
    err = np.linalg.norm(np.asarray(res.x)[:n] - xstar) / np.linalg.norm(xstar)
    assert err < 1e-4, (variant, err)
    assert bool(res.converged), variant
    assert float(res.resnorm) <= 1e-6, (variant, float(res.resnorm))
    print(f"{variant}:{float(res.iterations)}:{err:.2e}")
print("DIST_SAP_OK")
"""


def test_distributed_sap_solver_matches_dense():
    proc = _run(DIST_SAP)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DIST_SAP_OK" in proc.stdout
    # coupled variant must use fewer iterations than decoupled
    lines = dict(
        (ln.split(":")[0], float(ln.split(":")[1]))
        for ln in proc.stdout.splitlines()
        if ln.startswith(("C:", "D:"))
    )
    assert lines["C"] <= lines["D"]


DIST_E_F64 = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import SaPOptions, factor, plan_banded
from repro.core.banded import band_to_dense, oscillatory_banded
from repro.core.distributed import build_dist_sap, solve_step_fn
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((2, 4), ("data", "model"))
n, k = 600, 6
band = oscillatory_banded(n, k, d=0.5, seed=0)  # non-decaying spikes
A = np.asarray(band_to_dense(jnp.asarray(band)))
xstar = np.random.default_rng(0).normal(size=n)
b = A @ xstar

# sharded: "auto" estimates d from shard-local rows and must pick E
dsap = build_dist_sap(mesh, n, k, variant="auto", p_per_device=2, band=band)
assert dsap.variant == "E", dsap.variant
assert abs(dsap.d_factor - 0.5) < 1e-6, dsap.d_factor
band_p, b_p, parts = dsap.shard_band(band, b)
step = solve_step_fn(dsap, tol=1e-8, maxiter=100)
with mesh:
    res = jax.jit(step)(band_p, b_p, parts["d"].astype(jnp.float64),
                        parts["e"].astype(jnp.float64),
                        parts["f"].astype(jnp.float64),
                        parts["b_next"].astype(jnp.float64),
                        parts["c_prev"].astype(jnp.float64))
assert bool(res.converged), (float(res.iterations), float(res.resnorm))
assert float(res.resnorm) <= 1e-8
x_dist = np.asarray(res.x)[:n]

# single-device exact reference at the same partition count
fac = factor(plan_banded(jnp.asarray(band),
                         SaPOptions(p=16, variant="E", tol=1e-8, maxiter=100,
                                    precond_dtype="float64")))
ref = fac.solve(jnp.asarray(b))
assert bool(ref.converged)
x_ref = np.asarray(ref.x)

err_x = np.linalg.norm(x_dist - x_ref) / np.linalg.norm(x_ref)
err_star = np.linalg.norm(x_dist - xstar) / np.linalg.norm(xstar)
assert err_x < 1e-6, err_x
assert err_star < 1e-6, err_star
assert abs(float(res.iterations) - float(ref.iterations)) <= 2.0
print(f"E_dist:{float(res.iterations)}:{err_x:.2e}:{err_star:.2e}")
print("DIST_E_F64_OK")
"""


def test_distributed_exact_variant_f64_agrees_with_single_device():
    """Acceptance: sharded variant E (distributed cyclic reduction) hits
    1e-8 in f64 on the d=0.5 oscillatory regime where truncated C stalls,
    and matches the single-device exact factorization."""
    proc = _run(DIST_E_F64, x64=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DIST_E_F64_OK" in proc.stdout


@pytest.mark.parametrize("mesh_flag", ["", "--multi-pod"])
def test_dryrun_cell_compiles_on_test_mesh(mesh_flag, tmp_path):
    """End-to-end dryrun driver on the scaled-down mesh (8 devices)."""
    out = tmp_path / "cell.json"
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", "sap-solver", "--shape", "dense_200k",
        "--out", str(out),
    ] + ([mesh_flag] if mesh_flag else [])
    env = {
        "PYTHONPATH": str(SRC),
        "REPRO_DRYRUN_DEVICES": "8",
        "JAX_PLATFORMS": "cpu",
        "PATH": "/usr/bin:/bin",
        "HOME": "/root",
    }
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    row = json.loads(out.read_text())
    assert row["roofline"]["flops"] > 0
    assert row["roofline"]["bottleneck"] in ("compute", "memory", "collective")


SOLVER_DRYRUN = r"""
import sys
sys.argv = ["dryrun", "--arch", "sap-solver", "--shape", "dense_200k"]
from repro.launch import dryrun
import json
import os
row = dryrun.lower_solver_cell("dense_200k", False, type("A", (), {
    "variant": "C", "p_per_device": 1, "save_hlo": None,
    "precond_dtype": "float32"})())
assert row["roofline"]["coll_bytes"] > 0  # ppermutes present
print("SOLVER_DRYRUN_OK")
"""


def test_solver_dryrun_has_neighbor_collectives():
    proc = _run(SOLVER_DRYRUN)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SOLVER_DRYRUN_OK" in proc.stdout


SOLVER_DRYRUN_E = r"""
from repro.launch import dryrun
row = dryrun.lower_solver_cell("dense_200k", False, type("A", (), {
    "variant": "E", "p_per_device": 1, "save_hlo": None,
    "precond_dtype": "float32"})())
assert row["kind"] == "solve-E" and row["chips"] == 8, row
assert row["roofline"]["coll_bytes"] > 0  # the PCR sweep's ppermutes
print("SOLVER_DRYRUN_E_OK")
"""


def test_solver_dryrun_variant_e_lowers_on_test_mesh():
    """Variant E (distributed cyclic reduction) lowers and compiles on
    the 8-device test mesh."""
    proc = _run(SOLVER_DRYRUN_E)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SOLVER_DRYRUN_E_OK" in proc.stdout


def _dryrun_cli(*args):
    env = dict(os.environ, PYTHONPATH=str(SRC), REPRO_DRYRUN_DEVICES="8",
               JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", *args],
        capture_output=True, text=True, timeout=300, env=env)


def test_dryrun_list_prints_solver_shapes():
    from repro.configs.sap_solver import SOLVER_SHAPES

    proc = _dryrun_cli("--list")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.splitlines() == [f"sap-solver x {s}"
                                        for s in SOLVER_SHAPES]


@pytest.mark.parametrize("args", [
    ("--arch", "stablelm-1.6b", "--shape", "dense_200k"),
    ("--zero1", "--shape", "dense_200k"),
    ("--variant", "E"),
], ids=["lm_arch", "lm_flag", "no_shape"])
def test_dryrun_cli_accepts_solver_cells_only(args):
    """The dry run lowers solver cells only: an LM arch, an LM flag or a
    missing --shape is a usage error (exit 2) before anything compiles."""
    proc = _dryrun_cli(*args)
    assert proc.returncode == 2, proc.stderr[-3000:]
    assert "usage:" in proc.stderr


MESH = r"""
import json
import os
from repro.launch.mesh import make_production_mesh
out = {}
for multi_pod in (False, True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    out[str(multi_pod)] = [list(mesh.axis_names), list(mesh.devices.shape)]
print(json.dumps(out))
"""


def test_production_mesh_scales_down_to_available_devices():
    """Below 256 / 512 devices the production mesh keeps its axis names
    and folds the devices into a (2, n/2) or (2, 2, n/4) stand-in."""
    proc = _run(MESH)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["False"] == [["data", "model"], [2, 4]]
    assert out["True"] == [["pod", "data", "model"], [2, 2, 2]]
