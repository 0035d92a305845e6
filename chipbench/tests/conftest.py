"""Fixtures of the chip benchmark's CPU tests.

Nothing here touches a TPU: the tests run the harness on JAX's CPU backend
with the Pallas kernels in interpret mode, at sizes a test run can hold.
"""

import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

# Each configuration cut to a size the interpreter runs in seconds.
TINY = {
    "sap_dense_200k_c": {"n": 1024, "k": 4, "p": 4},
}
TINY_TRAFFIC = {
    "fresh": {"rhs_pool": 4, "check_steps": 4},
}


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")


# Each traffic file with the configuration it runs under, as the cells pair them.
PAIRS = [("sap_dense_200k_c", "fresh")]


def tiny_cell(config: str, traffic: str):
    """A cell of this configuration and traffic, cut to a tiny size, with
    every metric of the benchmark."""
    import json

    from chipbench import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{config}.json").read_text())
    tr = json.loads((ROOT / "chipbench" / "traffic" / f"{traffic}.json").read_text())
    cfg.update(TINY[config])
    tr.update(TINY_TRAFFIC[traffic])
    return harness.Cell(name=f"{config}.{traffic}", chips=1, config=cfg, traffic=tr,
                        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


def run_tiny(config: str, traffic: str, seed: int = 7, seconds: float = 1.0) -> dict:
    from chipbench.run import run_cell

    return run_cell(tiny_cell(config, traffic), seed, seconds, False, CPU)
