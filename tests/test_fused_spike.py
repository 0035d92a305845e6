"""Fused factor+spike megakernel: parity vs the kernel sequence.

The fused pass (repro.kernels.fused_spike + its scan oracle in
repro.core.block_lu) must be *exactly* the algorithm the btf -> UL-btf ->
bts kernel sequence runs:

  * ``sinv`` / ``l`` / ``v_bot`` / ``w_top`` are the same recurrences in
    the same operation order -> bit-identical to the sequence.
  * ``v_top`` / ``w_bot`` are computed by forward carries instead of
    whole-spike back-substitution -> algebraically equal, compared with
    a float32 tolerance.

One deliberate shape quirk: at M = 1 the scan in ``btf_ref`` produces an
*empty* ``l`` of shape (P, 0, K, K), while the fused paths always emit the
explicit zero block (P, 1, K, K) the Pallas kernel writes at j = 0.  Both
are inert in every solve (``l[1:]`` is empty either way), so the parity
checks compare ``l`` only for M > 1.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.banded import band_to_block_tridiag, random_banded
from repro.core.batched import pad_band_to
from repro.core.block_lu import (
    btf_ref,
    btf_ul_ref,
    bts_ref,
    fused_factor_spike_ref,
    pad_couplings,
)
from repro.core.spike import build_preconditioner, resolve_fused
from repro.kernels import ops


def _chain(rng, p, m, k, dtype=jnp.float32):
    """Well-conditioned block-tridiag chain + off-partition couplings.

    The diagonal shift outgrows the spectral radius of a normal K x K
    block (about sqrt(K)) once K is past a few dozen."""
    r = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
    d = r(p, m, k, k) + max(4.0, k / 8) * jnp.eye(k, dtype=dtype)
    e = r(p, m, k, k) * 0.3
    f = r(p, m, k, k) * 0.3
    b_cpl = r(p - 1, k, k) * 0.3
    c_cpl = r(p - 1, k, k) * 0.3
    return d, e, f, b_cpl, c_cpl


def _sequence_oracle(d, e, f, b_cpl, c_cpl):
    """The kernel-sequence baseline: btf + UL-btf + whole-spike solves."""
    p, m, k, _ = d.shape
    lu = btf_ref(d, e, f)
    v_bot = lu.sinv[:-1, -1] @ b_cpl
    ul = btf_ul_ref(d, e, f)
    w_top = (ul.sinv[1:, -1] @ c_cpl[..., ::-1, :])[..., ::-1, :]
    rhs_b = jnp.zeros((p, m, k, k), d.dtype).at[:-1, -1].set(b_cpl)
    v_top = bts_ref(lu, rhs_b)[:-1, 0]
    rhs_c = jnp.zeros((p, m, k, k), d.dtype).at[1:, 0].set(c_cpl)
    w_bot = bts_ref(lu, rhs_c)[1:, -1]
    return lu, v_bot, v_top, w_top, w_bot


def _assert_corner_parity(fs, d, e, f, b_cpl, c_cpl):
    lu, v_bot, v_top, w_top, w_bot = _sequence_oracle(d, e, f, b_cpl, c_cpl)
    m = d.shape[1]
    # same recurrence, same op order -> bit-identical
    np.testing.assert_array_equal(np.asarray(fs.lu.sinv), np.asarray(lu.sinv))
    if m > 1:
        np.testing.assert_array_equal(np.asarray(fs.lu.l), np.asarray(lu.l))
    np.testing.assert_array_equal(np.asarray(fs.v_bot), np.asarray(v_bot))
    np.testing.assert_array_equal(np.asarray(fs.w_top), np.asarray(w_top))
    # forward carries vs back-substitution -> f32-allclose
    np.testing.assert_allclose(
        np.asarray(fs.v_top), np.asarray(v_top), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(fs.w_bot), np.asarray(w_bot), rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# reference (scan) formulation vs the kernel sequence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "p,m,k",
    [(2, 1, 3), (2, 4, 8), (3, 5, 3), (4, 3, 4), (5, 2, 2), (3, 7, 5),
     (2, 3, 136)],
)
def test_fused_ref_matches_sequence(p, m, k):
    """Non-pow2 grids included; M = 1 exercises the init-only path."""
    rng = np.random.default_rng(p * 100 + m * 10 + k)
    d, e, f, b_cpl, c_cpl = _chain(rng, p, m, k)
    fs = fused_factor_spike_ref(d, e, f, b_cpl, c_cpl)
    assert fs.v_bot.shape == (p - 1, k, k)
    assert fs.w_top.shape == (p - 1, k, k)
    _assert_corner_parity(fs, d, e, f, b_cpl, c_cpl)


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode) vs the scan reference: bit-level
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "p,m,k", [(2, 3, 4), (3, 5, 3), (2, 4, 8), (4, 1, 4), (2, 3, 136)]
)
def test_fused_kernel_interpret_bit_parity(p, m, k):
    rng = np.random.default_rng(7)
    d, e, f, b_cpl, c_cpl = _chain(rng, p, m, k)
    fr = ops.fused_factor_spike(d, e, f, b_cpl, c_cpl, impl="jnp")
    fk = ops.fused_factor_spike(d, e, f, b_cpl, c_cpl, impl="interpret")
    for name in ("v_bot", "v_top", "w_top", "w_bot"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fr, name)), np.asarray(getattr(fk, name)),
            err_msg=name)
    np.testing.assert_array_equal(
        np.asarray(fr.lu.sinv), np.asarray(fk.lu.sinv))
    np.testing.assert_array_equal(np.asarray(fr.lu.l), np.asarray(fk.lu.l))


def test_fused_kernel_matches_sequence_end_to_end():
    """interpret-mode kernel output vs the btf/bts sequence directly."""
    rng = np.random.default_rng(11)
    d, e, f, b_cpl, c_cpl = _chain(rng, 4, 4, 8)
    fk = ops.fused_factor_spike(d, e, f, b_cpl, c_cpl, impl="interpret")
    _assert_corner_parity(fk, d, e, f, b_cpl, c_cpl)


# ---------------------------------------------------------------------------
# batched (5-dim) dispatch: folded grid == per-system loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_fused_batched_fold_matches_per_system(impl):
    s, p, m, k = 3, 4, 3, 4
    rng = np.random.default_rng(13)
    ds, es, fs_, bs, cs = [], [], [], [], []
    for _ in range(s):
        d, e, f, b_cpl, c_cpl = _chain(rng, p, m, k)
        ds.append(d); es.append(e); fs_.append(f)
        bs.append(b_cpl); cs.append(c_cpl)
    D, E, F = jnp.stack(ds), jnp.stack(es), jnp.stack(fs_)
    B, C = jnp.stack(bs), jnp.stack(cs)
    out = ops.fused_factor_spike(D, E, F, B, C, impl=impl)
    assert out.v_bot.shape == (s, p - 1, k, k)
    for i in range(s):
        one = ops.fused_factor_spike(ds[i], es[i], fs_[i], bs[i], cs[i],
                                     impl=impl)
        np.testing.assert_array_equal(
            np.asarray(out.lu.sinv[i]), np.asarray(one.lu.sinv))
        for name in ("v_bot", "v_top", "w_top", "w_bot"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out, name)[i]),
                np.asarray(getattr(one, name)), err_msg=name)


def test_pad_couplings_zero_pad_isolates_fold():
    """Padded coupling slots are exactly zero -> spike corners of the pad
    slots are exactly zero, so the batch fold cannot cross-contaminate."""
    rng = np.random.default_rng(17)
    d, e, f, b_cpl, c_cpl = _chain(rng, 3, 2, 4)
    bq, cq = pad_couplings(b_cpl, c_cpl, 3)
    assert bq.shape == (3, 4, 4) and cq.shape == (3, 4, 4)
    np.testing.assert_array_equal(np.asarray(bq[-1]), 0.0)
    np.testing.assert_array_equal(np.asarray(cq[0]), 0.0)


# ---------------------------------------------------------------------------
# preconditioner / solve level: fused on == fused off
# ---------------------------------------------------------------------------


def test_resolve_fused_policy():
    assert resolve_fused("on", "jnp") is True
    assert resolve_fused(True, "jnp") is True
    assert resolve_fused("off", "pallas") is False
    assert resolve_fused(False, "pallas") is False
    assert resolve_fused(None, "pallas") is False
    assert resolve_fused("auto", "jnp") is False
    assert resolve_fused("auto", "interpret") is False
    assert resolve_fused("auto", "pallas") is True
    with pytest.raises(ValueError):
        resolve_fused("always", "jnp")


@pytest.mark.parametrize("variant", ["C", "E"])
def test_preconditioner_fused_on_off_equivalent(variant):
    band = jnp.asarray(random_banded(96, 3, 1.2, seed=3), jnp.float32)
    bt = band_to_block_tridiag(band, 3, 4)
    p_off = build_preconditioner(bt, variant=variant, fused="off")
    p_on = build_preconditioner(bt, variant=variant, fused="on")
    assert p_off.fused is False and p_on.fused is True
    rng = np.random.default_rng(5)
    r = jnp.asarray(rng.normal(size=96), jnp.float32)
    a_off, a_on = p_off.apply(r), p_on.apply(r)
    if variant == "C":
        # the C-ul path consumes only the bit-identical corners
        np.testing.assert_array_equal(np.asarray(a_off), np.asarray(a_on))
    else:
        np.testing.assert_allclose(
            np.asarray(a_off), np.asarray(a_on), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("variant", ["C", "E"])
def test_preconditioner_fused_padded_identity_bucket(variant):
    """Bucket padding (interleaved identity rows) stays exact under the
    fused pass: padded-system corners equal the unpadded system's via the
    structural-zero pivot exemption, same as the sequence path."""
    n, k = 80, 2
    band = np.float32(random_banded(n, k, 1.3, seed=9))
    padded = pad_band_to(jnp.asarray(band), 128, 4)
    bt = band_to_block_tridiag(jnp.asarray(padded), 4, 4)
    p_off = build_preconditioner(bt, variant=variant, fused="off")
    p_on = build_preconditioner(bt, variant=variant, fused="on")
    rng = np.random.default_rng(1)
    r = jnp.asarray(rng.normal(size=128), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(p_off.apply(r)), np.asarray(p_on.apply(r)),
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("variant", ["C", "E"])
def test_solve_fused_on_off_equivalent(variant):
    from repro.core import SaPOptions, factor, plan_banded
    from repro.core.banded import band_matvec

    band = jnp.asarray(random_banded(160, 4, 1.2, seed=21), jnp.float32)
    x = np.random.default_rng(2).normal(size=160)
    b = band_matvec(band, jnp.asarray(x, jnp.float32))
    res = {}
    for fused in ("off", "on"):
        opts = SaPOptions(p=4, variant=variant, tol=1e-6, maxiter=200,
                          fused_factor=fused)
        fac = factor(plan_banded(band, opts))
        assert fac.pc.fused is (fused == "on")
        res[fused] = fac.solve(b)
        assert bool(res[fused].converged)
        assert float(res[fused].true_resnorm) < 1e-5
    np.testing.assert_allclose(
        np.asarray(res["off"].x), np.asarray(res["on"].x),
        rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# property test (hypothesis, optional dependency)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # optional dependency: CI installs it, the image may not
    HAS_HYPOTHESIS = False

if HAS_HYPOTHESIS:

    @settings(deadline=None, max_examples=15, print_blob=True)
    @given(
        p=st.integers(min_value=2, max_value=5),
        m=st.integers(min_value=1, max_value=6),
        k=st.integers(min_value=1, max_value=8),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_fused_ref_parity_property(p, m, k, seed):
        """For any chain shape: exact parity on the LU half, f32-allclose
        on the carried spike corners (jnp ref vs kernel sequence)."""
        rng = np.random.default_rng(seed)
        d, e, f, b_cpl, c_cpl = _chain(rng, p, m, k)
        fs = fused_factor_spike_ref(d, e, f, b_cpl, c_cpl)
        _assert_corner_parity(fs, d, e, f, b_cpl, c_cpl)

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_fused_ref_parity_property():
        pass
