"""Compile the solver's Pallas kernels for a TPU v5e that is described,
not attached.

``jax.experimental.topologies`` describes a ``v5e:2x2`` slice; each kernel
is lowered with ``interpret=False`` onto one of its chips and compiled by
the TPU compiler that ships with jaxlib.  That catches what interpret mode
cannot: primitives Mosaic does not lower (dynamic slices, ``rev``),
unaligned tiles and VMEM overruns.  Nothing runs, so these tests say
nothing about results or speed.

Shapes are the cells' real ones: K = 16 is the fleet/service bucket
(N = 16,384, P = 8), K = 200 is ``dense_200k`` (N = 200,000, P = 16, so
M = 63 blocks per partition and a (P-1, 2K) = (15, 400) reduced chain).

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and every test
worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bcr import bcr_factor_pallas, bcr_solve_pallas
from repro.kernels.btf import btf_pallas
from repro.kernels.bts import bts_pallas
from repro.kernels.fused_spike import fused_factor_spike_pallas

# (P, M, K) per cell; the E reduced chain is (P - 1) blocks of 2K.
SHAPES = {"k16": (8, 128, 16), "k200": (16, 63, 200)}
NRHS = 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _btf(p, m, k):
    blk = (p, m, k, k)
    return (lambda d, e, f: btf_pallas(d, e, f, interpret=False)), [blk] * 3


def _bts(p, m, k):
    blk = (p, m, k, k)
    return (
        lambda s, l, f, b: bts_pallas(s, l, f, b, interpret=False),
        [blk] * 3 + [(p, m, k, NRHS)],
    )


def _fused(p, m, k):
    blk = (p, m, k, k)
    return (
        lambda d, e, f, bq, cq: fused_factor_spike_pallas(
            d, e, f, bq, cq, interpret=False
        ),
        [blk] * 3 + [(p, k, k)] * 2,
    )


def _bcr_factor(p, m, k):
    chain = (p - 1, 2 * k, 2 * k)
    return (lambda d, e, f: bcr_factor_pallas(d, e, f, interpret=False),
            [chain] * 3)


def _bcr_solve(p, m, k):
    chain = (p - 1, 2 * k, 2 * k)

    def fn(d, e, f, b):
        fac = bcr_factor_pallas(d, e, f, interpret=False)
        return bcr_solve_pallas(fac, b, interpret=False)

    return fn, [chain] * 3 + [(p - 1, 2 * k, NRHS)]


KERNELS = {
    "btf": _btf,
    "bts": _bts,
    "fused_spike": _fused,
    "bcr_factor": _bcr_factor,
    "bcr_solve": _bcr_solve,
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, shape):
    fn, arg_shapes = KERNELS[kernel](*SHAPES[shape])
    args = [
        jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
        for s in arg_shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bts_compiles_for_v5e_under_vmap_at_fleet_shape(one_chip):
    """The fleet's path: the engine vmaps the solve over its 64 systems, so
    Pallas puts the system axis in front of the (P // pt, M) grid.  At
    (16, 64, 16, 16), R 1, all 16 partition chains share a grid step; a
    tile that overran scoped VMEM would fail here."""
    s, p, m, k = 64, 16, 64, 16
    fn = jax.vmap(lambda a, l, f, b: bts_pallas(a, l, f, b, interpret=False))
    args = [
        jax.ShapeDtypeStruct(x, jnp.float32, sharding=one_chip)
        for x in [(s, p, m, k, k)] * 3 + [(s, p, m, k, 1)]
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
