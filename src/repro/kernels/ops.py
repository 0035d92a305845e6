"""Jit'd public wrappers for the Pallas kernels with implementation dispatch.

The kernels are ``btf`` (block-tridiagonal factor), ``bts`` (block
solves), ``fused_spike`` (factor + spike corners in one pass) and ``bcr``
(block cyclic reduction for the SaP-E interface chain).

``impl`` selects the execution path:
  * "jnp"       -- pure-jnp reference (default on CPU; identical math)
  * "interpret" -- Pallas kernel executed in interpret mode (CPU-validated)
  * "pallas"    -- compiled Pallas TPU kernel (the production path)

The default comes from the env var ``REPRO_KERNEL_IMPL`` when it is set
(an unknown value raises); otherwise it is "pallas" when JAX's first
device is a TPU and "jnp" on any other backend.

All three paths share :func:`repro.core.block_lu.gj_inverse` and with it
the structural-zero pivot exemption: exactly-zero block rows (identity
padding from shape bucketing) take pivot 1 instead of a boosted ``thr``,
so ``boost_eps`` only ever perturbs *numerically* small pivots.
"""

from __future__ import annotations

import os

import jax

from repro.core.block_lu import (
    DEFAULT_BOOST,
    BTFactors,
    FusedSpikeFactors,
    fused_factor_spike_padded_ref,
    pad_couplings,
)
from repro.core.cyclic_reduction import BCRFactors

from . import ref
from .bcr import bcr_factor_pallas, bcr_solve_pallas
from .btf import btf_pallas
from .bts import bts_pallas
from .fused_spike import fused_factor_spike_pallas


IMPLS = ("jnp", "interpret", "pallas")


def default_impl() -> str:
    """Kernel backend: REPRO_KERNEL_IMPL if set, else "pallas" on TPU, "jnp"."""
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        if env not in IMPLS:
            raise ValueError(
                f"REPRO_KERNEL_IMPL={env!r} is not one of {IMPLS}"
            )
        return env
    return "pallas" if jax.devices()[0].platform == "tpu" else "jnp"


def _interpret(impl: str) -> bool:
    """Pallas execution mode for a kernel impl: interpreted or compiled."""
    if impl == "interpret":
        return True
    if impl == "pallas":
        return False
    raise ValueError(f"no Pallas kernel runs under impl={impl!r}")


# ---------------------------------------------------------------------------
# Block-tridiagonal factor / solve
# ---------------------------------------------------------------------------
#
# Both entry points are batch-aware: a 5-dim input carries a leading
# *system* axis (S, P, M, K, K) -- a fleet of independent block-tridiagonal
# systems (repro.core.batched).  Partitions are already an embarrassingly
# parallel grid axis, so the batch axis FOLDS into it: the Pallas kernels
# run one grid of S*P independent chains (a real batch grid axis, not a
# silent per-system fallback), and the jnp reference path vectorizes over
# the same folded axis.


def _fold_batch(x: jax.Array) -> jax.Array:
    """(S, P, ...) -> (S*P, ...): batch systems become extra partitions."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def _unfold_batch(x: jax.Array, s: int) -> jax.Array:
    return x.reshape((s, x.shape[0] // s) + x.shape[1:])


def block_tridiag_factor(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    boost_eps: float = DEFAULT_BOOST,
    impl: str | None = None,
) -> BTFactors:
    """Block-tridiagonal LU factor of (P, M, K, K) chains; 5-D input batches."""
    impl = impl or default_impl()
    if d.ndim == 5:  # batched (S, P, M, K, K): fold batch into the grid
        s = d.shape[0]
        fac = block_tridiag_factor(
            _fold_batch(d), _fold_batch(e), _fold_batch(f), boost_eps, impl
        )
        return BTFactors(
            sinv=_unfold_batch(fac.sinv, s),
            l=_unfold_batch(fac.l, s),
            f=_unfold_batch(fac.f, s),
        )
    if impl == "jnp":
        return ref.btf_ref(d, e, f, boost_eps)
    sinv, l = btf_pallas(d, e, f, boost_eps, interpret=_interpret(impl))
    return BTFactors(sinv=sinv, l=l, f=f)


def block_tridiag_solve(
    factors: BTFactors, b: jax.Array, impl: str | None = None
) -> jax.Array:
    """Solve the factored chains for (P, M, K, R) right-hand sides."""
    impl = impl or default_impl()
    if b.ndim == 5:  # batched (S, P, M, K, R): fold batch into the grid
        s = b.shape[0]
        folded = BTFactors(
            sinv=_fold_batch(factors.sinv),
            l=_fold_batch(factors.l),
            f=_fold_batch(factors.f),
        )
        return _unfold_batch(block_tridiag_solve(folded, _fold_batch(b), impl), s)
    if impl == "jnp":
        return ref.bts_ref(factors, b)
    return bts_pallas(
        factors.sinv, factors.l, factors.f, b, interpret=_interpret(impl)
    )


def block_tridiag_factor_chain(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    boost_eps: float = DEFAULT_BOOST,
    impl: str | None = None,
) -> BTFactors:
    """Factor a single block-tridiagonal chain (M, K, K).

    The recursive entry point for the SaP-E exact reduced interface system:
    the (P-1) coupled 2Kx2K interface blocks form one chain, factored by
    the same kernel as the partition factorization (grid (1, M)).

    A 4-dim input (S, M, K, K) is a *batch* of independent chains -- which
    is exactly the (P, M, K, K) partition layout, so the batch rides the
    parallel grid axis for free.
    """
    if d.ndim == 4:  # batched chains: the batch axis IS the partition axis
        return block_tridiag_factor(d, e, f, boost_eps, impl=impl)
    return block_tridiag_factor(d[None], e[None], f[None], boost_eps, impl=impl)


def block_tridiag_solve_chain(
    factors: BTFactors, b: jax.Array, impl: str | None = None
) -> jax.Array:
    """Solve one factored chain: b (M, K, R) -> x (M, K, R).

    b of 4 dims (S, M, K, R) solves a batch of factored chains (the
    batch axis rides the parallel partition grid axis).
    """
    if b.ndim == 4:
        return block_tridiag_solve(factors, b, impl=impl)
    return block_tridiag_solve(factors, b[None], impl=impl)[0]


# ---------------------------------------------------------------------------
# Fused factor + spike megakernel (one pass, four VMEM carries)
# ---------------------------------------------------------------------------


def _fused_padded(d, e, f, bq, cq, boost_eps, impl):
    if impl == "jnp":
        return fused_factor_spike_padded_ref(d, e, f, bq, cq, boost_eps)
    return fused_factor_spike_pallas(
        d, e, f, bq, cq, boost_eps, interpret=_interpret(impl)
    )


def fused_factor_spike(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    b_cpl: jax.Array,
    c_cpl: jax.Array,
    boost_eps: float = DEFAULT_BOOST,
    impl: str | None = None,
) -> FusedSpikeFactors:
    """Fused block-LU factor + spike-corner extraction in one pass.

    Replaces the btf -> UL-btf -> bts kernel *sequence* of the SaP factor
    stage: one grid over (partition, block-row) computes the LU factors
    AND all four spike corner blocks (v_bot / v_top / w_top / w_bot),
    carrying the UL recurrence and both spike right-hand sides in VMEM
    instead of materializing UL factors and whole K-column spikes in HBM
    (see :mod:`repro.kernels.fused_spike`).

    d/e/f: (P, M, K, K) partition blocks; b_cpl/c_cpl: (P-1, K, K)
    interface couplings.  A 5-dim input (S, P, M, K, K) with (S, P-1, K, K)
    couplings is a fleet of systems: the batch axis folds into the
    partition grid like :func:`block_tridiag_factor`.

    ``lu`` / ``v_bot`` / ``w_top`` are bit-identical to the sequence
    formulation; ``v_top`` / ``w_bot`` are algebraically equal (different
    rounding -- forward carries instead of whole-spike back-substitution).
    """
    impl = impl or default_impl()
    b_cpl = b_cpl.astype(d.dtype)
    c_cpl = c_cpl.astype(d.dtype)
    if d.ndim == 5:  # batched (S, P, M, K, K): fold batch into the grid
        s, p = d.shape[0], d.shape[1]
        bq, cq = pad_couplings(b_cpl, c_cpl, p)  # (S, P, K, K)
        out = _fused_padded(
            _fold_batch(d), _fold_batch(e), _fold_batch(f),
            _fold_batch(bq), _fold_batch(cq), boost_eps, impl,
        )
        sinv, l, vb, vt, wt, wb = (_unfold_batch(x, s) for x in out)
        return FusedSpikeFactors(
            lu=BTFactors(sinv=sinv, l=l, f=f),
            v_bot=vb[:, :-1], v_top=vt[:, :-1],
            w_top=wt[:, 1:], w_bot=wb[:, 1:],
        )
    p = d.shape[0]
    bq, cq = pad_couplings(b_cpl, c_cpl, p)
    sinv, l, vb, vt, wt, wb = _fused_padded(d, e, f, bq, cq, boost_eps, impl)
    return FusedSpikeFactors(
        lu=BTFactors(sinv=sinv, l=l, f=f),
        v_bot=vb[:-1], v_top=vt[:-1], w_top=wt[1:], w_bot=wb[1:],
    )


# ---------------------------------------------------------------------------
# Block cyclic reduction (log-depth chain factor / solve)
# ---------------------------------------------------------------------------


def bcr_factor(
    d: jax.Array,
    e: jax.Array,
    f: jax.Array,
    boost_eps: float = DEFAULT_BOOST,
    impl: str | None = None,
) -> BCRFactors:
    """Factor a chain (M, K, K) by even/odd elimination in log2(M) levels.

    Log-depth alternative to :func:`block_tridiag_factor_chain` for the
    SaP-E reduced interface system; both impls build the identical
    :class:`~repro.core.cyclic_reduction.BCRFactors` pytree.
    """
    impl = impl or default_impl()
    if impl == "jnp":
        from repro.core import cyclic_reduction as cr

        return cr.bcr_factor(d, e, f, boost_eps)
    return bcr_factor_pallas(d, e, f, boost_eps, interpret=_interpret(impl))


def bcr_solve(
    factors: BCRFactors, b: jax.Array, impl: str | None = None
) -> jax.Array:
    """Solve one BCR-factored chain: b (M, K, R) -> x (M, K, R)."""
    impl = impl or default_impl()
    if impl == "jnp":
        from repro.core import cyclic_reduction as cr

        return cr.bcr_solve(factors, b)
    return bcr_solve_pallas(factors, b, interpret=_interpret(impl))


# ---------------------------------------------------------------------------
# Analytic FLOP models (the cost observatory's sanity anchors)
# ---------------------------------------------------------------------------
#
# Leading-order algebraic flop counts of the solver kernels above, used by
# repro.obs.cost tests to keep the HLO-derived counters honest: the HLO
# walk counts every lowered elementwise op (selects, boosts, masks), so it
# lands above these, but only by a bounded constant factor -- a blown-up
# ratio means the analyzer (or the kernel) regressed.


def gj_inverse_flops(k: int) -> float:
    """Gauss-Jordan inverse of one KxK block: ~2 K^3 multiply-adds."""
    return 2.0 * k**3


def btf_flops(p: int, m: int, k: int) -> float:
    """Block-tridiag factor of P chains of M KxK blocks.

    Per interior block: one Schur-pivot inverse (2 K^3), the elimination
    product ``l = e @ sinv`` (2 K^3), and the Schur update ``d - l @ f``
    (2 K^3 + K^2).
    """
    return float(p) * m * (gj_inverse_flops(k) + 4.0 * k**3 + k * k)


def bts_flops(p: int, m: int, k: int, r: int = 1) -> float:
    """Block-tridiag solve: forward + backward sweeps, three K x K block
    mat-vecs (2 K^2 R each) per block per sweep pair."""
    return float(p) * m * 6.0 * k * k * r


def fused_factor_spike_flops(p: int, m: int, k: int) -> float:
    """Fused factor+spike megakernel: the LU recurrence twice (forward and
    reversed chains, ~6 K^3 + K^2 per block each), two K x K RHS carries
    (2 K^3 per block each), plus four corner products (2 K^3 each) per
    partition.  Compare ~2x the flops of :func:`btf_flops` alone -- but
    the kernel *sequence* it replaces pays the UL factor writeback and two
    whole-spike bts solves in HBM traffic, which is what the fused pass
    eliminates (see ``solver_stage_costs``)."""
    return 2.0 * btf_flops(p, m, k) + float(p) * m * 4.0 * k**3 + float(p) * 8.0 * k**3


def bcr_flops(m: int, k: int) -> float:
    """Cyclic reduction over a chain of M KxK blocks: ~M eliminated nodes
    across the log2(M) levels, each paying one inverse (2 K^3) and four
    update products (2 K^3 each)."""
    return float(m) * 10.0 * k**3
