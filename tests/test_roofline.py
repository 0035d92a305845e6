"""Roofline / HLO-analysis unit tests (calibrated against XLA on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_stats import analyze_hlo, _parse_type
from repro.launch.roofline import (
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS,
    analyze,
)


def _hlo(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


def test_type_bytes():
    assert _parse_type("f32[8,128]{1,0}")[0] == 8 * 128 * 4
    assert _parse_type("bf16[2,2]")[0] == 8
    assert _parse_type("(f32[4], s32[2])")[0] == 16 + 8
    assert _parse_type("pred[]")[0] == 1


def test_matmul_flops_exact():
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    st = analyze_hlo(_hlo(lambda x: x @ x, a))
    assert st.flops == pytest.approx(2 * 256**3, rel=0.01)


def test_scan_trip_count_multiplies():
    a = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def scanned(x):
        def body(c, _):
            return c @ c * 0.5, None
        y, _ = jax.lax.scan(body, x, None, length=12)
        return y

    st = analyze_hlo(_hlo(scanned, a))
    assert st.flops == pytest.approx(12 * 2 * 128**3, rel=0.05)


def test_grad_flops_roughly_double():
    a = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def lossf(w, x):
        return jnp.sum((x @ w) ** 2)

    st = analyze_hlo(_hlo(lambda w, x: jax.grad(lossf)(w, x), a, a))
    assert st.flops == pytest.approx(2 * 2 * 128**3, rel=0.1)


def test_remat_adds_recompute_flops():
    ws = jax.ShapeDtypeStruct((6, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def make(remat):
        def lossf(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            b = jax.checkpoint(body) if remat else body
            h, _ = jax.lax.scan(b, x, ws)
            return jnp.sum(h**2)

        return lambda ws, x: jax.grad(lossf)(ws, x)

    plain = analyze_hlo(_hlo(make(False), ws, x)).flops
    remat = analyze_hlo(_hlo(make(True), ws, x)).flops
    assert remat > plain * 1.2  # ~4/3x expected


def test_analyze_bottleneck_selection():
    roof = analyze({}, "", chips=256, model_flops_global=0.0)
    assert roof.bottleneck in ("compute", "memory", "collective")
    # compute-dominated synthetic numbers
    hlo = ""  # empty -> all zero; construct directly instead
    from repro.launch.roofline import Roofline

    assert PEAK_FLOPS > HBM_BW > ICI_BW


def test_collective_parse_shard_map_psum():
    """A hand-built psum inside shard_map must appear as all-reduce bytes.
    Uses the 1-device trivial mesh: XLA still emits the op metadata-free,
    so run on the real parser via a crafted HLO snippet instead."""
    hlo = """HloModule test, is_scheduled=true

ENTRY %main.1 (p0: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128]{1,0} parameter(0)
  ROOT %all-reduce.1 = f32[128,128]{1,0} all-reduce(%p0), replica_groups={}, to_apply=%add
}
"""
    st = analyze_hlo(hlo)
    assert st.coll["all-reduce"]["bytes"] == 128 * 128 * 4
    assert st.coll["all-reduce"]["count"] == 1
