"""The check fails the control and each fault the cells can have.

The control runs every matrix product of the solver at XLA's ``high``
precision, one step below the configuration's float32 at HIGHEST; the
fault alters an answer where the solver produces it.  Each drives the
whole run (set-up, window, check) at a tiny size on the CPU and must come
out not correct.
"""

import pytest

from chipbench import control
from chipbench.tests.conftest import PAIRS, run_tiny, tiny_cell


@pytest.mark.parametrize("plant", ["control", "answer"])
@pytest.mark.parametrize("config,traffic", PAIRS)
def test_check_fails_the_planted_fault(config, traffic, plant, interpret):
    with control.PLANTS[plant](tiny_cell(config, traffic).config):
        rec = run_tiny(config, traffic, seed=11)
    assert rec["correct"] is False
    assert rec["failed"] > 0
    c = rec["compared"]["max_residual"]
    assert c["value"] > c["limit"]


def test_plants_restore_the_solver():
    from repro.core import block_lu, sap, spike

    cfg = tiny_cell(*PAIRS[0]).config
    before = (block_lu.mm, spike.mm, sap._solve_many)
    for plant in ("control", "answer"):
        with control.PLANTS[plant](cfg):
            pass
    assert (block_lu.mm, spike.mm, sap._solve_many) == before


def test_control_follows_the_stated_precision():
    cfg = tiny_cell(*PAIRS[0]).config
    assert cfg["matmul_precision"] in control.LOWER
    with pytest.raises(KeyError):
        control.plant_control({**cfg, "matmul_precision": "default"})
