"""Import boundary: every package of ``repro`` imports solver code only.

Each case imports one package in a fresh interpreter and lists the
``repro`` modules it loaded; every one must belong to a solver package,
and every kernel module to a solver kernel.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parent.parent / "src"

SOLVER_PACKAGES = {"core", "kernels", "serve", "obs", "configs", "launch",
                   "compile_cache"}
SOLVER_KERNELS = {"ops", "ref", "btf", "bts", "fused_spike", "bcr"}

_SCRIPT = """
import importlib, json, sys
importlib.import_module({mod!r})
print(json.dumps(sorted(m for m in sys.modules
                        if m == "repro" or m.startswith("repro."))))
"""


@pytest.mark.parametrize("mod", [
    "repro.core", "repro.kernels", "repro.serve", "repro.obs",
    "repro.configs", "repro.launch.dryrun",
])
def test_package_imports_only_solver_modules(mod):
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu",
               REPRO_DRYRUN_DEVICES="8")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT.format(mod=mod)],
                          capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    loaded = json.loads(proc.stdout.strip().splitlines()[-1])
    assert mod in loaded
    parts = [m.split(".") for m in loaded if m != "repro"]
    bad = [".".join(p) for p in parts
           if p[1] not in SOLVER_PACKAGES
           or (p[1] == "kernels" and len(p) > 2 and p[2] not in SOLVER_KERNELS)]
    assert bad == []
