"""``chip_smoke.py`` at a tiny size on CPU, and the no-fallback guarantees
it relies on.

The phase functions take their sizes, so the dense, exact and served
phases run here with the Pallas kernels interpreted
(``REPRO_KERNEL_IMPL=interpret``).  The script itself must refuse to run
without a TPU and without the repo's package next to it.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compile_cache
from repro.configs import sap_solver
from repro.core.banded import oscillatory_banded, random_banded
from repro.kernels import ops
from repro.obs import NULL_SPAN, Tracer

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")


# ---------------------------------------------------------------------------
# phases at a tiny size
# ---------------------------------------------------------------------------


def test_host_references_match_dense_algebra():
    n, k = 50, 3
    band = random_banded(n, k, 1.0, seed=4)
    b = np.random.default_rng(5).standard_normal((n, 2))
    x = chip_smoke.scipy_solve(band, b)
    dense = np.zeros((n, n))
    for r in range(n):
        for j in range(2 * k + 1):
            if 0 <= r - k + j < n:
                dense[r, r - k + j] = band[r, j]
    np.testing.assert_allclose(x, np.linalg.solve(dense, b), rtol=1e-10)
    assert np.all(chip_smoke.host_residual(band, x, b) < 1e-13)
    x_bad = x + 1e-3
    assert np.all(chip_smoke.host_residual(band, x_bad, b) > 1e-5)


@pytest.mark.parametrize(
    "cfg,generator,solver",
    [
        (sap_solver.full(), random_banded, "auto"),
        (sap_solver.exact(), oscillatory_banded, "refine"),
    ],
    ids=["C", "E"],
)
def test_dense_phase_tiny(interpret, cfg, generator, solver):
    cfg = dataclasses.replace(cfg, n=2000, k=8)
    rep = chip_smoke.dense_phase(f"dense_{cfg.variant}", cfg, p=16, nrhs=2,
                                 tol=1e-6, seed=0, generator=generator,
                                 solver=solver)
    assert rep["impl"] == "interpret" and rep["fused"] is False
    assert rep["variant"] == cfg.variant
    assert max(rep["host_f64_residual"]) <= 1e-5
    assert max(rep["rel_err_vs_scipy"]) < 1e-3
    if cfg.variant == "E":
        assert rep["reduced_solver"] == "bcr"


def test_served_phase_tiny(interpret):
    cfg = dataclasses.replace(sap_solver.service(), n=512, k=4)
    rep = chip_smoke.served_phase(cfg, p=8, n_matrices=4, per_matrix=2,
                                  seed=0, timeout_s=600.0)
    assert rep["requests"] == 8
    assert sorted(set(rep["variants"])) == ["C", "E"]
    assert max(rep["host_f64_residual"]) <= 10 * cfg.tol
    assert rep["cache_hit_rate"] > 0


_FOUR_CHIP_TINY = """
import json, sys
sys.path.insert(0, {repo!r})
import chip_smoke
from repro.configs.sap_solver import SolverShape
out = chip_smoke.four_chip_phase(
    SolverShape("big", 4000, 10), SolverShape("small", 2000, 8),
    p_per_device=4, tol=1e-6, seed=0, agree=1e-5)
print(json.dumps(out))
"""


def test_four_chip_phase_tiny_on_host_devices():
    """The --four-chips phase on 4 CPU host devices (a 2x2 mesh)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIP_TINY.format(repo=str(REPO))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["C"]["variant"] == "C" and rep["auto"]["variant"] == "E"
    for name in ("C", "auto", "compare"):
        assert rep[name]["host_f64_residual"] <= 1e-5
    assert rep["compare"]["rel_diff_vs_single_chip"] <= 1e-5


def test_phase_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke._check(False, "boom")


# ---------------------------------------------------------------------------
# no fallbacks
# ---------------------------------------------------------------------------


def _run_script(script: Path, cwd: Path, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run_script(REPO / "chip_smoke.py", REPO, env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_script_fails_without_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    # even with the package importable from elsewhere
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    out = _run_script(tmp_path / "chip_smoke.py", tmp_path, env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not the one next to" in out.stderr


@pytest.mark.parametrize("value", ["pallas ", "cuda", "JNP"])
def test_unknown_kernel_impl_raises(monkeypatch, value):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", value)
    with pytest.raises(ValueError, match="REPRO_KERNEL_IMPL"):
        ops.default_impl()


@pytest.mark.parametrize("value", ops.IMPLS)
def test_default_impl_honours_valid_env(monkeypatch, value):
    """A valid REPRO_KERNEL_IMPL wins over the backend default."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", value)
    assert ops.default_impl() == value


def test_default_impl_on_cpu(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
    assert ops.default_impl() == "jnp"
    with pytest.raises(ValueError, match="impl='jnp'"):
        ops._interpret("jnp")


# ---------------------------------------------------------------------------
# spans under tracing, and sync errors
# ---------------------------------------------------------------------------


def test_span_under_jit_does_not_sync_a_tracer():
    tr = Tracer()
    seen = []

    @jax.jit
    def f(x):
        with tr.span("inside") as sp:
            seen.append(sp)
            y = sp.sync(x * 2.0)
        return y

    assert float(f(jnp.float32(3.0))) == 6.0
    assert seen == [NULL_SPAN]
    assert tr.roots() == []


class _Broken:
    """A pytree leaf whose sync fails, as a device error would."""

    def block_until_ready(self):
        raise RuntimeError("device lost")


def test_span_sync_error_propagates():
    tr = Tracer()
    with pytest.raises(RuntimeError, match="device lost"):
        with tr.span("outer"):
            with tr.span("inner") as sp:
                sp.sync(_Broken())
    (root,) = tr.roots()  # both spans still closed, nesting intact
    assert [c.name for c in root.children] == ["inner"]


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
