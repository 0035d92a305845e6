"""SPIKE machinery: truncated spikes, reduced system, SaP preconditioner.

Implements paper Sec. 2.1:

  * right-spike bottom blocks   V_i^(b) = Sinv_i[M-1] @ B_i          (2.2a)
  * left-spike top blocks       W_{i+1}^(t) via the UL factorization (2.2c)
  * the truncated reduced system (2.9):
        Rbar_i               = I - W_{i+1}^(t) V_i^(b)
        Rbar_i xt_{i+1}^(t)  = g_{i+1}^(t) - W_{i+1}^(t) g_i^(b)
        xt_i^(b)             = g_i^(b) - V_i^(b) xt_{i+1}^(t)
  * the final decoupled solves (2.10).

Three preconditioner variants (paper Sec. 2.1.1):
  * SaP-D  ("decoupled"): z = D^{-1} r, one block solve.
  * SaP-C  ("coupled"):   block solve + truncated-spike correction +
                          second block solve.
  * SaP-E  ("exact"):     block solve + *exact* reduced-system correction +
                          second block solve.  The truncation in (2.9) rests
                          on spike decay, which requires diagonal dominance
                          (d >= 1, Eq. 2.11); SaP-E instead assembles the
                          full (P-1)-interface reduced system from whole
                          spikes -- a block-tridiagonal chain of (2K x 2K)
                          blocks -- and factors it with the same btf/bts
                          stack used for the partitions (recursively, so
                          the Pallas kernel dispatch covers it too).  The
                          apply is then an exact solve of the banded
                          preconditioner matrix, robust for d < 1 at the
                          cost of the extra O(P K^3) reduced factor.

Reduced system (exact; unknowns y_i = [x_i^(b); x_{i+1}^(t)], i = 0..P-2):

    [ I            V_i^(b) ]        [ W_i^(b) 0 ]        [ 0  0          ]
    [ W_{i+1}^(t)  I       ] y_i  + [ 0       0 ] y_{i-1} + [ 0  V_{i+1}^(t) ] y_{i+1}
        = [ g_i^(b); g_{i+1}^(t) ]

where V_i = A_i^{-1}[0;..;B_i] and W_i = A_i^{-1}[C_i;0;..] are the whole
spikes (their top/bottom K x K blocks appear above).  Truncating the
off-diagonal terms recovers (2.9).

Every block inversion here goes through
:func:`repro.core.block_lu.gj_inverse`, whose structural-zero exemption
keeps identity-padded slots (shape bucketing) exactly identity: the
coupling blocks B/C of a padded embedding are zero on padded rows, so the
spikes -- and hence the reduced system -- of blkdiag(A, I) decouple
exactly instead of picking up boosted ``1/thr`` perturbations.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .banded import BlockTridiag
from .block_lu import (
    DEFAULT_BOOST,
    BTFactors,
    FusedSpikeFactors,
    btf_chain,
    btf_ref,
    btf_ul_ref,
    bts_chain,
    bts_ref,
    fused_factor_spike_ref,
    gj_inverse,
    mm,
)
from ..obs.trace import span
from .cyclic_reduction import (
    BCRFactors,
    bcr_factor,
    bcr_solve,
    resolve_reduced_solver,
)


def _flip_rows(x: jax.Array) -> jax.Array:
    return x[..., ::-1, :]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "lu", "b_cpl", "c_cpl", "v_bot", "w_top", "rbar_inv", "red_lu",
        "red_bcr",
    ),
    meta_fields=("variant", "p", "m", "k", "impl", "reduced_solver", "fused"),
)
@dataclasses.dataclass
class SaPPreconditioner:
    """Factored SaP preconditioner ('C' coupled, 'D' decoupled, 'E' exact).

    All factor arrays may be stored in a lower precision than the Krylov
    iteration (paper Sec. 3.1 "Mixed Precision Strategy").
    """

    variant: str  # "C" | "D" | "E"
    lu: BTFactors  # factors of diag(A_1..A_P)
    b_cpl: jax.Array  # (P-1, K, K)
    c_cpl: jax.Array  # (P-1, K, K)
    v_bot: Optional[jax.Array]  # (P-1, K, K)  V_i^(b)
    w_top: Optional[jax.Array]  # (P-1, K, K)  W_{i+1}^(t)
    rbar_inv: Optional[jax.Array]  # (P-1, K, K)  inv(I - W V)
    red_lu: Optional[BTFactors]  # factors of the exact (P-1, 2K) reduced chain
    red_bcr: Optional[BCRFactors]  # log-depth BCR factors of the same chain
    p: int
    m: int
    k: int
    impl: str = "jnp"  # kernel dispatch: "jnp" | "interpret" | "pallas"
    # resolved reduced-chain solver for variant E: "chain" (sequential
    # btf/bts sweep) or "bcr" (log-depth cyclic reduction); "none" otherwise
    reduced_solver: str = "none"
    # True when the factor+spike stage ran as the fused single-pass
    # megakernel instead of the btf -> UL -> bts kernel sequence
    fused: bool = False

    def apply(self, r: jax.Array) -> jax.Array:
        """Apply M^{-1} to a (padded) flat residual of length P*M*K."""
        dtype = self.lu.sinv.dtype
        rb = r.astype(dtype).reshape(self.p, self.m, self.k, -1)
        if self.variant == "D":
            z = _bts(self.lu, rb, self.impl)
        elif self.variant == "E":
            z = _apply_exact(self, rb)
        else:
            z = _apply_coupled(self, rb)
        return z.reshape(r.shape).astype(r.dtype)


def _bts(factors, b, impl):
    """Solve through the kernel dispatch layer (lazy import: no cycles)."""
    if impl == "jnp":
        return bts_ref(factors, b)
    from repro.kernels import ops as kops

    return kops.block_tridiag_solve(factors, b, impl=impl)


def _btf(d, e, f, boost_eps, impl):
    if impl == "jnp":
        return btf_ref(d, e, f, boost_eps)
    from repro.kernels import ops as kops

    return kops.block_tridiag_factor(d, e, f, boost_eps, impl=impl)


def _btf_chain(d, e, f, boost_eps, impl):
    """Factor one block-tridiag chain (M, K, K) through the same dispatch."""
    if impl == "jnp":
        return btf_chain(d, e, f, boost_eps)
    from repro.kernels import ops as kops

    return kops.block_tridiag_factor_chain(d, e, f, boost_eps, impl=impl)


def _bts_chain(factors, b, impl):
    if impl == "jnp":
        return bts_chain(factors, b)
    from repro.kernels import ops as kops

    return kops.block_tridiag_solve_chain(factors, b, impl=impl)


def _bcr_factor(d, e, f, boost_eps, impl):
    """Log-depth chain factor through the same dispatch (ref/interpret/pallas)."""
    if impl == "jnp":
        return bcr_factor(d, e, f, boost_eps)
    from repro.kernels import ops as kops

    return kops.bcr_factor(d, e, f, boost_eps, impl=impl)


def _bcr_solve(factors, b, impl):
    if impl == "jnp":
        return bcr_solve(factors, b)
    from repro.kernels import ops as kops

    return kops.bcr_solve(factors, b, impl=impl)


def _fused_factor_spike(d, e, f, b_cpl, c_cpl, boost_eps, impl):
    """Fused factor+spike megakernel through the same dispatch."""
    if impl == "jnp":
        return fused_factor_spike_ref(d, e, f, b_cpl, c_cpl, boost_eps)
    from repro.kernels import ops as kops

    return kops.fused_factor_spike(d, e, f, b_cpl, c_cpl, boost_eps, impl=impl)


def resolve_fused(fused, impl: str) -> bool:
    """Resolve the ``fused_factor`` knob: ``"auto"`` means fused on the
    compiled kernel path (where the VMEM carries actually avoid HBM
    round trips) and the kernel-sequence formulation elsewhere."""
    if fused in (True, "on"):
        return True
    if fused in (None, False, "off"):
        return False
    if fused == "auto":
        return impl == "pallas"
    raise ValueError(f"unknown fused_factor setting {fused!r}")


def _apply_coupled(pc: SaPPreconditioner, rb: jax.Array) -> jax.Array:
    # 1) g = D^{-1} r
    g = _bts(pc.lu, rb, pc.impl)  # (P, M, K, R)
    g_top = g[:, 0]  # (P, K, R)
    g_bot = g[:, -1]  # (P, K, R)

    # 2) reduced-system correction per interface i = 0..P-2   (eq. 2.9)
    rhs = g_top[1:] - mm(pc.w_top, g_bot[:-1])  # (P-1, K, R)
    xt_top = mm(pc.rbar_inv, rhs)  # xt_{i+1}^(t)
    xt_bot = g_bot[:-1] - mm(pc.v_bot, xt_top)  # xt_i^(b)

    # 3) final solves (eq. 2.10): subtract coupling contributions
    top_corr = mm(pc.c_cpl, xt_bot)  # into partitions 1..P-1, top block
    bot_corr = mm(pc.b_cpl, xt_top)  # into partitions 0..P-2, bottom block
    rb2 = rb
    rb2 = rb2.at[1:, 0].add(-top_corr)
    rb2 = rb2.at[:-1, -1].add(-bot_corr)
    return _bts(pc.lu, rb2, pc.impl)


def _apply_exact(pc: SaPPreconditioner, rb: jax.Array) -> jax.Array:
    """SaP-E apply: an exact solve of the banded preconditioner matrix."""
    # 1) g = D^{-1} r
    g = _bts(pc.lu, rb, pc.impl)  # (P, M, K, R)

    # 2) exact reduced system on the interface unknowns y_i = [x_i^(b);
    #    x_{i+1}^(t)]; the RHS is just the interface slices of g (the spike
    #    blocks live in the factored chain, not in the RHS).
    h = jnp.concatenate([g[:-1, -1], g[1:, 0]], axis=1)  # (P-1, 2K, R)
    if pc.reduced_solver == "bcr":
        y = _bcr_solve(pc.red_bcr, h, pc.impl)
    else:
        y = _bts_chain(pc.red_lu, h, pc.impl)
    xt_bot = y[:, : pc.k]  # x_i^(b),     i = 0..P-2
    xt_top = y[:, pc.k :]  # x_{i+1}^(t), i = 0..P-2

    # 3) final solves (eq. 2.10), now with exact interface values
    rb2 = rb.at[1:, 0].add(-mm(pc.c_cpl, xt_bot))
    rb2 = rb2.at[:-1, -1].add(-mm(pc.b_cpl, xt_top))
    return _bts(pc.lu, rb2, pc.impl)


def _reduced_interface_system(v_bot, v_top, w_top, w_bot):
    """Assemble the exact (P-1)-interface block-tridiag chain (2K blocks).

    Inputs are the four corner blocks of the whole spikes, each (P-1, K, K):
    v_bot/v_top index right spikes of partitions 0..P-2, w_top/w_bot left
    spikes of partitions 1..P-1.  Returns (d, e, f) of shape
    (P-1, 2K, 2K); e[0] / f[P-2] are unused by the factorization.
    """
    dtype = v_bot.dtype
    q, k, _ = v_bot.shape  # q = P-1 interfaces
    eye = jnp.broadcast_to(jnp.eye(k, dtype=dtype), (q, k, k))
    zero = jnp.zeros((q, k, k), dtype)

    def blk2(tl, tr, bl, br):
        top = jnp.concatenate([tl, tr], axis=-1)
        bot = jnp.concatenate([bl, br], axis=-1)
        return jnp.concatenate([top, bot], axis=-2)

    # y_{i-1} contributes W_i^(b) x_{i-1}^(b); y_{i+1} contributes
    # V_{i+1}^(t) x_{i+2}^(t) (see module docstring).
    shift_dn = lambda x: jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)
    shift_up = lambda x: jnp.concatenate([x[1:], jnp.zeros_like(x[:1])], 0)
    rd = blk2(eye, v_bot, w_top, eye)
    re = blk2(shift_dn(w_bot), zero, zero, zero)
    rf = blk2(zero, zero, zero, shift_up(v_top))
    return rd, re, rf


def build_preconditioner(
    bt: BlockTridiag,
    variant: str = "C",
    boost_eps: float = DEFAULT_BOOST,
    precond_dtype=jnp.float32,
    impl: str = "jnp",
    spike_mode: str = "ul",
    reduced_solver: str = "auto",
    fused: str | bool = "off",
) -> SaPPreconditioner:
    """Factor the SaP preconditioner from block-tridiagonal partitions.

    spike_mode:
      * "ul"   -- paper Sec. 2.1 fast path: V^(b) from the bottom of the LU
                  factors, W^(t) from a UL factorization (top only).
      * "full" -- compute the *entire* spikes by full solves and take the
                  needed blocks.  This is the paper's third-stage-reordering
                  path (Sec. 2.2.1: per-partition reordering "renders the UL
                  factorization superfluous" and mandates whole spikes).
      Variant "E" always uses whole spikes (it needs all four corner
      blocks), so ``spike_mode`` is ignored there.

    reduced_solver (variant "E" only; carried on the returned pytree and
    echoed into ``SaPSolution.info``):
      * "chain" -- sequential btf/bts sweep over the (P-1)-interface chain
                   (O(P) dependent steps).
      * "bcr"   -- block cyclic reduction: O(log2 P) parallel levels
                   (``repro.core.cyclic_reduction``), same kernel dispatch.
      * "auto"  -- "bcr" once the chain is long enough to amortize the
                   log-depth machinery, else "chain".

    fused (``"on"`` / ``"off"`` / ``"auto"``; bools accepted): run the
    factor AND spike-corner extraction as ONE fused pass
    (:func:`repro.kernels.ops.fused_factor_spike`) instead of the
    btf -> UL-btf -> bts kernel sequence.  ``"auto"`` resolves to fused on
    the compiled kernel path (``impl="pallas"``), where the UL recurrence
    and spike carries stay in VMEM instead of round-tripping HBM.  The
    fused pass is UL-based, so it applies to variants C/E with P > 1 under
    ``spike_mode="ul"``; it produces bit-identical ``lu`` / ``v_bot`` /
    ``w_top`` and algebraically equal ``v_top`` / ``w_bot`` (forward
    carries instead of whole-spike back-substitution).
    """
    if variant not in ("C", "D", "E"):
        raise ValueError(f"unknown SaP variant {variant!r}")
    if spike_mode not in ("ul", "full"):
        raise ValueError(f"unknown spike_mode {spike_mode!r}")
    reduced_solver = (
        resolve_reduced_solver(reduced_solver, bt.p - 1)
        if variant == "E" and bt.p > 1
        else "none"
    )
    use_fused = (
        resolve_fused(fused, impl)
        and variant in ("C", "E")
        and spike_mode == "ul"
        and bt.p > 1
    )
    d = bt.d.astype(precond_dtype)
    e = bt.e.astype(precond_dtype)
    f = bt.f.astype(precond_dtype)
    b_cpl = bt.b_cpl.astype(precond_dtype)
    c_cpl = bt.c_cpl.astype(precond_dtype)

    v_bot = w_top = rbar_inv = red_lu = red_bcr = None
    v_top = w_bot = None
    # Spans degrade to no-ops under jit/vmap tracing (the batched factor
    # stages call this inside vmap), so host timing only covers eager calls.
    if use_fused:
        with span(
            "factor.fused", p=bt.p, m=bt.m, k=bt.k, variant=variant, impl=impl
        ) as sp:
            fs: FusedSpikeFactors = _fused_factor_spike(
                d, e, f, b_cpl, c_cpl, boost_eps, impl
            )
            lu = fs.lu
            v_bot, w_top = fs.v_bot, fs.w_top
            v_top, w_bot = fs.v_top, fs.w_bot
            sp.sync((v_bot, w_top))
    else:
        with span("factor.lu", p=bt.p, m=bt.m, k=bt.k, impl=impl) as sp:
            lu = sp.sync(_btf(d, e, f, boost_eps, impl))

    if variant in ("C", "E") and bt.p > 1:
        if not use_fused:
            with span("factor.spike", variant=variant, mode=spike_mode) as sp:
                if variant == "C" and spike_mode == "ul":
                    # V_i^(b) = Sinv_i[M-1] @ B_i  for i = 0..P-2
                    v_bot = mm(lu.sinv[:-1, -1], b_cpl)
                    # W_{i+1}^(t) from the UL factorization of partitions
                    # 1..P-1
                    ul = btf_ul_ref(d, e, f, boost_eps)
                    w_top = _flip_rows(mm(ul.sinv[1:, -1], _flip_rows(c_cpl)))
                else:
                    # whole right spikes: A_i V_i = [0;..;B_i], keep corners
                    rhs_b = jnp.zeros((bt.p, bt.m, bt.k, bt.k), precond_dtype)
                    rhs_b = rhs_b.at[:-1, -1].set(b_cpl)
                    v_full = _bts(lu, rhs_b, impl)
                    v_bot = v_full[:-1, -1]
                    v_top = v_full[:-1, 0]
                    # whole left spikes: A_{i+1} W_{i+1} = [C_{i+1};0;..]
                    rhs_c = jnp.zeros((bt.p, bt.m, bt.k, bt.k), precond_dtype)
                    rhs_c = rhs_c.at[1:, 0].set(c_cpl)
                    w_full = _bts(lu, rhs_c, impl)
                    w_top = w_full[1:, 0]
                    w_bot = w_full[1:, -1]
                sp.sync((v_bot, w_top))
        if variant == "C":
            with span("factor.reduced", solver="truncated") as sp:
                eye = jnp.eye(bt.k, dtype=precond_dtype)
                rbar = eye - mm(w_top, v_bot)
                rbar_inv = jax.vmap(lambda a: gj_inverse(a, boost_eps))(rbar)
                sp.sync(rbar_inv)
        else:
            # exact reduced system: a (P-1)-long chain of 2K x 2K blocks,
            # factored either with the same block-tridiag stack
            # (recursively, O(P) sequential sweep) or by block cyclic
            # reduction (O(log2 P) parallel levels).
            with span("factor.reduced", solver=reduced_solver) as sp:
                rd, re, rf = _reduced_interface_system(
                    v_bot, v_top, w_top, w_bot
                )
                if reduced_solver == "bcr":
                    red_bcr = _bcr_factor(rd, re, rf, boost_eps, impl)
                    sp.sync(red_bcr)
                else:
                    red_lu = _btf_chain(rd, re, rf, boost_eps, impl)
                    sp.sync(red_lu)
    elif variant in ("C", "E"):
        variant = "D"  # single partition: coupled/exact == decoupled

    return SaPPreconditioner(
        variant=variant,
        lu=lu,
        b_cpl=b_cpl,
        c_cpl=c_cpl,
        v_bot=v_bot,
        w_top=w_top,
        rbar_inv=rbar_inv,
        red_lu=red_lu,
        red_bcr=red_bcr,
        p=bt.p,
        m=bt.m,
        k=bt.k,
        impl=impl,
        reduced_solver=reduced_solver,
        fused=use_fused,
    )
