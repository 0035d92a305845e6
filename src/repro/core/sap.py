"""SaP::TPU solver API: the plan / factor / solve lifecycle.

The paper's economics (Fig. 3.1) are: pay once for the expensive stages --
DB reordering (T_DB), CM reordering (T_CM), drop-off (T_Drop), banded
assembly (T_Asmbl) and the split block-LU + SPIKE factorization (T_LU) --
then amortize them over a cheap preconditioned Krylov iteration per
right-hand side (T_Kry).  The public API mirrors that lifecycle:

1. ``plan(A, opts) -> SaPPlan``
       Host-side analysis.  Accepts a :class:`~repro.core.operators.
       LinearOperator`, a host CSR / scipy matrix, or a dense square
       array; band storage goes through :func:`plan_banded`.  Computes the
       DB/CM permutations, drop-off, bandwidth, and the preconditioner
       band exactly once; permutations become part of the plan.

2. ``factor(plan) -> SaPFactorization``
       Device-side block-LU + truncated-SPIKE coupling (paper Sec. 2.1).
       The result is a registered JAX pytree: it can be passed through
       ``jax.jit`` boundaries, stored, and reused across any number of
       right-hand sides.

3. ``factorization.solve(b)`` / ``factorization.solve_many(B)``
       Pure JAX, jit-cached, vmap-compatible.  ``solve`` takes one RHS of
       shape (N,); ``solve_many`` takes (N, R) and runs an independent
       Krylov iteration per column (converged columns freeze while
       stragglers iterate).  Permutations are applied and undone inside.

The Krylov matvec always uses the *original* (reordered) matrix; drop-off
and the banded approximation only affect the preconditioner.  Mixed
precision (Sec. 3.1): the preconditioner is factored and applied in
``opts.precond_dtype`` while the outer iteration runs in the dtype of the
input RHS (override with ``opts.iter_dtype``).

``solve_banded`` and ``solve_sparse`` remain as thin one-shot wrappers for
backwards compatibility.  They re-run the whole pipeline on every call and
are **deprecated** for repeated solves -- use the lifecycle above when the
operator is reused.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import span
from . import reorder as reorder_mod
from .banded import band_to_block_tridiag, diag_dominance_factor
from .block_lu import DEFAULT_BOOST
from .krylov import KrylovResult, _bicgstab2_impl, _cg_impl, _refine_impl
from .operators import (
    BandedOperator,
    CsrOperator,
    LinearOperator,
    require_square_dense,
)
from .spike import SaPPreconditioner, build_preconditioner


@dataclasses.dataclass
class SaPOptions:
    """Solver configuration: partitioning, variant, tolerances, dtypes."""

    p: int = 8  # number of partitions
    # "C" coupled (truncated SPIKE) | "D" decoupled | "E" exact reduced
    # system | "auto" (C when the preconditioner band is diagonally
    # dominant, d >= 1, else E -- paper Sec. 2.1.1 guidance).  Resolution
    # happens at factor() time from the planned preconditioner band.
    variant: str = "C"
    tol: float = 1e-10
    maxiter: int = 500
    # Tolerance on the *true* relative residual ||b - A x|| / ||b|| a
    # result must meet before a ``converged`` claim is trusted: the Krylov
    # loop controls the preconditioned residual, and an inexact
    # preconditioner can meet ``tol`` while the true residual is large
    # (misconvergence).  None means 10 * tol.  Consumed by the serving
    # guard (SolverEngine / AsyncSolverService), which escalates or
    # demotes ``converged`` when the check fails; the core solve paths
    # always report ``true_resnorm`` so callers can apply their own check.
    check_true_residual: Optional[float] = None
    boost_eps: float = DEFAULT_BOOST
    precond_dtype: str = "float32"
    iter_dtype: Optional[str] = None  # Krylov dtype; None = follow the RHS
    use_cg: bool = False  # CG for SPD systems
    # Outer solver: "bicgstab2" | "cg" | "refine" (preconditioned iterative
    # refinement -- the mixed-precision play: factor in precond_dtype=f32,
    # refine in iter_dtype=f64 to full f64 accuracy) | "auto" (= "cg" when
    # use_cg else "bicgstab2"; use_cg remains as the legacy spelling).
    solver: str = "auto"
    # Fused factor+spike megakernel: "on" | "off" | "auto" (fused on the
    # compiled Pallas path, kernel sequence elsewhere).  See
    # repro.kernels.fused_spike; resolved at factor() time.
    fused_factor: str = "auto"
    # reduced-system solver for variant "E": "chain" = sequential btf/bts
    # sweep over the (P-1)-interface chain, "bcr" = log-depth block cyclic
    # reduction, "auto" = bcr once the chain is long enough to amortize it.
    reduced_solver: str = "auto"
    # sparse front-end (Sec. 2.2)
    use_db: bool = True  # diagonal-boosting reordering
    use_cm: bool = True  # bandwidth-reducing reordering
    third_stage: bool = False  # per-partition CM (Sec. 4.3.2)
    drop_tol: float = 0.0  # element drop-off fraction (0 = keep all)
    # Record the per-sweep Krylov residual history (observability).  A
    # solve-time knob only: it never enters the factorization pytree or any
    # cache key, so flipping it cannot fragment the engine's LRU or change
    # the compiled history-free executables.
    record_history: bool = False


@dataclasses.dataclass
class SaPSolution:
    """Legacy one-shot result (``solve_banded`` / ``solve_sparse``)."""

    x: np.ndarray | jax.Array
    iterations: float
    resnorm: float
    converged: bool
    k: int  # half bandwidth used by the preconditioner
    info: dict
    true_resnorm: float = float("nan")  # ||b - A x|| / ||b||, unpreconditioned


class SaPSolveResult(NamedTuple):
    """Result of a lifecycle solve; a pytree of device arrays.

    For ``solve_many``, ``x`` is (N, R) and the per-RHS diagnostics
    (``iterations`` / ``resnorm`` / ``converged``) are (R,).  ``d_factor``
    is the degree of diagonal dominance of the preconditioner band
    (paper Eq. 2.11, a scalar shared by all RHS) -- the quantity that
    drives the ``variant="auto"`` policy; the resolved variant itself is
    static metadata, available as ``factorization.variant``.

    Residual semantics: ``converged`` / ``resnorm`` are statements about
    the *preconditioned* residual ``M^-1 (b - A x)`` -- the quantity the
    Krylov iteration drives below ``tol``.  ``true_resnorm`` is the
    unpreconditioned ``||b - A x|| / ||b||`` recomputed at exit against
    the operator actually solved; when the preconditioner is inexact
    (e.g. a structurally-degraded padded embedding) the two can disagree,
    and ``true_resnorm`` is the one that measures answer quality.
    """

    x: jax.Array
    iterations: jax.Array
    resnorm: jax.Array
    converged: jax.Array
    true_resnorm: Optional[jax.Array] = None
    d_factor: Optional[jax.Array] = None
    # (maxiter,) per-sweep preconditioned residuals, NaN-padded -- or
    # (R, maxiter) for solve_many.  None unless record_history was requested.
    history: Optional[jax.Array] = None


def _precond_dtype(opts: SaPOptions):
    return {"float32": jnp.float32, "float64": jnp.float64, "bfloat16": jnp.bfloat16}[
        opts.precond_dtype
    ]


def _resolve_iter_dtype(b_dtype, iter_dtype: Optional[str]):
    """Krylov iteration dtype: explicit option > RHS dtype > canonical float.

    Never silently requests float64 in a non-x64 session (jax would
    truncate it anyway); integer/bool RHS promote to the canonical float.
    """
    x64 = jax.config.read("jax_enable_x64")
    if iter_dtype is not None:
        dt = np.dtype(iter_dtype)
    elif jnp.issubdtype(b_dtype, jnp.floating):
        dt = np.dtype(b_dtype)
    else:
        dt = np.dtype(np.float64 if x64 else np.float32)
    if dt == np.dtype(np.float64) and not x64:
        dt = np.dtype(np.float32)
    return dt


# ---------------------------------------------------------------------------
# Stage 1: plan (host-side analysis; runs the reordering pipeline once)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SaPPlan:
    """Host-side analysis result: operator + permutations + precond band.

    op      : reordered operator the Krylov matvec uses
    band_pc : (N, 2K+1) preconditioner band (post drop-off), device array
    k       : preconditioner half bandwidth
    b_perm  : RHS permutation (None = identity), ``b_r = b[b_perm]``
    x_perm  : unknown un-permutation (None = identity), ``x = x_r[x_perm]``
    opts    : solver options the factorization will inherit
    info    : stage diagnostics (db/cm flags, k_after_reorder, ...)
    """

    op: LinearOperator
    band_pc: jax.Array
    k: int
    n: int
    b_perm: Optional[np.ndarray]
    x_perm: Optional[np.ndarray]
    opts: SaPOptions
    info: dict


def plan_banded(band, opts: Optional[SaPOptions] = None) -> SaPPlan:
    """Plan for a dense banded system in (N, 2K+1) band storage.

    No reordering: the matrix is already banded (paper Sec. 4.1); the band
    itself is the preconditioner matrix.
    """
    opts = opts or SaPOptions()
    op = band if isinstance(band, BandedOperator) else BandedOperator.from_band(band)
    return SaPPlan(
        op=op,
        band_pc=op.band,
        k=op.k,
        n=op.n,
        b_perm=None,
        x_perm=None,
        opts=opts,
        info={"variant": opts.variant, "p": opts.p},
    )


def plan(a, opts: Optional[SaPOptions] = None) -> SaPPlan:
    """Plan for a general operator / sparse matrix (paper Sec. 2.2 / 4.3).

    Runs DB + CM reordering and drop-off once (per ``opts``); the returned
    plan carries the permutations, the reordered operator, and the
    preconditioner band.  Banded operators skip the reordering front end.
    """
    opts = opts or SaPOptions()
    if isinstance(a, BandedOperator):
        return plan_banded(a, opts)
    if isinstance(a, CsrOperator):
        a = a.to_csr()
    elif isinstance(a, (np.ndarray, jax.Array)):
        require_square_dense(a)

    with span("plan", use_db=opts.use_db, use_cm=opts.use_cm) as sp:
        rp = reorder_mod.analyze(
            a, use_db=opts.use_db, use_cm=opts.use_cm, drop_tol=opts.drop_tol
        )
        sp.annotate(n=rp.csr.n, k=rp.k)
    op = CsrOperator.from_csr(rp.csr)
    canonical = jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32
    return SaPPlan(
        op=op,
        band_pc=jnp.asarray(rp.band_pc, canonical),
        k=rp.k,
        n=rp.csr.n,
        b_perm=rp.b_perm,
        x_perm=rp.x_perm,
        opts=opts,
        info={**rp.info, "variant": opts.variant, "p": opts.p},
    )


# ---------------------------------------------------------------------------
# Stage 2: factor (device-side block-LU + SPIKE; returns a reusable handle)
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("op", "pc", "b_perm", "x_perm", "d_factor"),
    meta_fields=("n", "k", "tol", "maxiter", "use_cg", "iter_dtype", "solver"),
)
@dataclasses.dataclass(eq=False)
class SaPFactorization:
    """Reusable SaP factorization handle (a registered JAX pytree).

    Holds the reordered operator, the factored preconditioner, and the
    permutations; ``solve`` / ``solve_many`` are pure JAX and jit-cached,
    so repeated right-hand sides pay only the Krylov iteration.

    ``d_factor`` (degree of diagonal dominance of the preconditioner band,
    paper Eq. 2.11) is carried as a device scalar -- a *data* field, so
    factorizations of different matrices share one compiled solve -- and
    echoed into every :class:`SaPSolveResult`.  The variant actually
    factored (after ``"auto"`` resolution) is ``self.variant``.
    """

    op: LinearOperator
    pc: SaPPreconditioner
    b_perm: Optional[jax.Array]  # int32 (N,) or None (identity)
    x_perm: Optional[jax.Array]  # int32 (N,) or None (identity)
    n: int
    k: int
    tol: float
    maxiter: int
    use_cg: bool
    iter_dtype: Optional[str]
    # resolved outer solver ("bicgstab2" | "cg" | "refine"); never "auto"
    solver: str = "bicgstab2"
    d_factor: Optional[jax.Array] = None  # scalar, Eq. 2.11 estimate

    @property
    def variant(self) -> str:
        """Variant actually factored ("auto" resolved): "C", "D", or "E"."""
        return self.pc.variant

    @property
    def p(self) -> int:
        """Number of partitions in the factorization."""
        return self.pc.p

    @property
    def n_pad(self) -> int:
        """Internal (padded) problem size P*M*K; >= the user's N."""
        return self.pc.p * self.pc.m * self.pc.k

    def solve(self, b: jax.Array, record_history: bool = False) -> SaPSolveResult:
        """Solve A x = b for a single RHS of shape (N,).

        ``record_history=True`` additionally returns the per-sweep Krylov
        residual track on ``result.history`` (a separate jit cache entry;
        the default path's compiled executable is untouched).
        """
        b = jnp.asarray(b)
        if b.ndim != 1:
            raise ValueError(
                f"solve expects a single RHS of shape ({self.n},), got "
                f"{b.shape}; use solve_many for batched (N, R) systems"
            )
        if b.shape[0] != self.n:
            raise ValueError(f"RHS length {b.shape[0]} != operator size {self.n}")
        with span(
            "krylov", n=self.n, k=self.k, p=self.p, variant=self.variant, nrhs=1
        ) as sp:
            res = sp.sync(_solve_one(self, b, record_history=record_history))
        if sp:
            sp.annotate(convergence=_convergence_summary(res))
        return res

    def solve_many(self, b: jax.Array, record_history: bool = False) -> SaPSolveResult:
        """Solve A X = B for B of shape (N, R): one Krylov run per column."""
        b = jnp.asarray(b)
        if b.ndim != 2:
            raise ValueError(
                f"solve_many expects shape ({self.n}, R), got {b.shape}; "
                f"use solve for a single (N,) RHS"
            )
        if b.shape[0] != self.n:
            raise ValueError(f"RHS length {b.shape[0]} != operator size {self.n}")
        with span(
            "krylov",
            n=self.n,
            k=self.k,
            p=self.p,
            variant=self.variant,
            nrhs=int(b.shape[1]),
        ) as sp:
            res = sp.sync(_solve_many(self, b, record_history=record_history))
        if sp:
            sp.annotate(convergence=_convergence_summary(res))
        return res


def resolve_solver(solver: str, use_cg: bool) -> str:
    """Resolve ``SaPOptions.solver`` to a concrete outer solver name.

    ``"auto"`` honors the legacy ``use_cg`` flag; explicit names win over
    it.  The result is what ``SaPFactorization.solver`` carries.
    """
    if solver == "auto":
        return "cg" if use_cg else "bicgstab2"
    if solver not in ("bicgstab2", "cg", "refine"):
        raise ValueError(f"unknown solver {solver!r}")
    return solver


def resolve_variant(variant: str, d_factor: float) -> str:
    """The ``"auto"`` policy: truncated SPIKE needs spike decay, which the
    paper ties to diagonal dominance (Sec. 2.1.1) -- pick the cheap
    truncated variant C for d >= 1, the exact reduced system E otherwise.
    """
    if variant != "auto":
        return variant
    return "C" if d_factor >= 1.0 else "E"


def factor(pl: SaPPlan) -> SaPFactorization:
    """Factor the SaP preconditioner from a plan (T_LU .. T_SPIKE).

    Device-side and done once; the returned handle is reusable across any
    number of ``solve`` / ``solve_many`` calls and jit boundaries.
    ``variant="auto"`` is resolved here from the planned preconditioner
    band's degree of diagonal dominance (C for d >= 1, else E).  The
    kernel implementation is :func:`repro.kernels.ops.default_impl`:
    the compiled Pallas kernels on a TPU, the jnp references elsewhere.
    """
    from ..kernels.ops import default_impl  # lazy: repro.kernels imports core

    opts = pl.opts
    impl = default_impl()
    with span("factor", n=pl.n, k=pl.k, p=opts.p, impl=impl) as sp:
        d_factor = diag_dominance_factor(pl.band_pc)
        variant = resolve_variant(opts.variant, float(d_factor))
        sp.annotate(variant=variant, d_factor=float(d_factor))
        with span("factor.split"):
            bt = band_to_block_tridiag(pl.band_pc, max(pl.k, 1), opts.p)
        pc = build_preconditioner(
            bt,
            variant=variant,
            boost_eps=opts.boost_eps,
            precond_dtype=_precond_dtype(opts),
            impl=impl,
            reduced_solver=opts.reduced_solver,
            fused=opts.fused_factor,
        )
        sp.sync(pc)
    to_idx = lambda p: None if p is None else jnp.asarray(p, jnp.int32)
    return SaPFactorization(
        op=pl.op,
        pc=pc,
        b_perm=to_idx(pl.b_perm),
        x_perm=to_idx(pl.x_perm),
        n=pl.n,
        k=pl.k,
        tol=opts.tol,
        maxiter=opts.maxiter,
        use_cg=opts.use_cg,
        iter_dtype=opts.iter_dtype,
        solver=resolve_solver(opts.solver, opts.use_cg),
        d_factor=d_factor,
    )


# ---------------------------------------------------------------------------
# Stage 3: solve (pure JAX; jit-cached module-level entry points)
# ---------------------------------------------------------------------------


def _solve_impl(
    fac: SaPFactorization, b: jax.Array, record_history: bool = False
) -> SaPSolveResult:
    """Single-RHS solve body: permute, Krylov, un-permute (all on device)."""
    dt = _resolve_iter_dtype(b.dtype, fac.iter_dtype)
    b = b.astype(dt)
    if fac.b_perm is not None:
        b = b[fac.b_perm]

    n, n_pad = fac.n, fac.n_pad

    def precond(r):
        # named_scope (not a host span): this runs under jit/vmap, and the
        # scope name groups the preconditioner-apply ops in XLA profiles so
        # the in-device precond-vs-matvec split is readable there.
        with jax.named_scope("sap.precond_apply"):
            rp = (
                jnp.concatenate([r, jnp.zeros((n_pad - n,), r.dtype)])
                if n_pad != n
                else r
            )
            return fac.pc.apply(rp)[:n]

    if fac.solver == "refine":
        solver = _refine_impl
    elif fac.solver == "cg" or fac.use_cg:
        solver = _cg_impl
    else:
        solver = _bicgstab2_impl
    with jax.named_scope("sap.krylov"):
        res: KrylovResult = solver(
            fac.op.matvec,
            b,
            precond=precond,
            tol=fac.tol,
            maxiter=fac.maxiter,
            record_history=record_history,
        )
    x = res.x[fac.x_perm] if fac.x_perm is not None else res.x
    # true_resnorm is computed in the solver frame (permuted / padded),
    # but permutations preserve norms and exact identity-padding rows
    # contribute a zero residual, so it equals the original-frame
    # ||b - A x|| / ||b|| of the unpadded, unpermuted system.
    return SaPSolveResult(
        x=x,
        iterations=res.iterations,
        resnorm=res.resnorm,
        converged=res.converged,
        true_resnorm=res.true_resnorm,
        d_factor=fac.d_factor,
        history=res.history,
    )


_solve_one = jax.jit(_solve_impl, static_argnames=("record_history",))


@partial(jax.jit, static_argnames=("record_history",))
def _solve_many(
    fac: SaPFactorization, bmat: jax.Array, record_history: bool = False
) -> SaPSolveResult:
    # d_factor is shared by all RHS (closed over, unbatched): out_axes None
    out_axes = SaPSolveResult(
        x=1, iterations=0, resnorm=0, converged=0, true_resnorm=0,
        d_factor=None,
        history=0 if record_history else None,
    )
    return jax.vmap(
        lambda bi: _solve_impl(fac, bi, record_history), in_axes=1, out_axes=out_axes
    )(bmat)


def _convergence_summary(res: SaPSolveResult) -> dict:
    """Host-side convergence digest for the ``krylov`` span attribute."""
    out = {
        "iterations": float(np.max(np.asarray(res.iterations))),
        "converged": bool(np.all(np.asarray(res.converged))),
        "resnorm": float(np.max(np.asarray(res.resnorm))),
    }
    if res.history is not None:
        hist = np.atleast_2d(np.asarray(res.history))
        firsts, lasts, recorded, stalled = [], [], 0, False
        for row in hist:
            rec = row[~np.isnan(row)]
            recorded = max(recorded, rec.size)
            if rec.size == 0:
                continue
            firsts.append(float(rec[0]))
            lasts.append(float(rec[-1]))
            # Stall heuristic: <10% progress over the last 5 recorded sweeps.
            if rec.size >= 5 and rec[-1] > 0.9 * rec[-5]:
                stalled = True
        out["recorded"] = recorded
        if firsts:
            out["first_resnorm"] = max(firsts)
            out["last_resnorm"] = max(lasts)
        out["stalled"] = bool(stalled and not out["converged"])
    return out


# ---------------------------------------------------------------------------
# Legacy one-shot wrappers (deprecated for repeated solves)
# ---------------------------------------------------------------------------


def _warn_one_shot(name: str, replacement: str) -> None:
    # Python's default "once per location" warning filter dedups this;
    # stacklevel=3 points at the caller of the public wrapper.
    warnings.warn(
        f"{name} re-runs the whole plan/factor pipeline on every call and "
        f"is deprecated; use {replacement} and reuse the handle across "
        f"right-hand sides (repro.core.sap lifecycle API)",
        DeprecationWarning,
        stacklevel=3,
    )


def solve_banded(
    band: jax.Array,
    b: jax.Array,
    opts: Optional[SaPOptions] = None,
) -> SaPSolution:
    """One-shot solve of a dense banded system in (N, 2K+1) band storage.

    Deprecated for repeated solves: this re-plans and re-factors on every
    call.  Use ``factor(plan_banded(band, opts))`` and reuse the handle.
    """
    _warn_one_shot("solve_banded", "factor(plan_banded(band, opts)).solve(b)")
    pl = plan_banded(band, opts)
    fac = factor(pl)
    res = fac.solve(jnp.asarray(b))
    return SaPSolution(
        x=res.x,
        iterations=float(res.iterations),
        resnorm=float(res.resnorm),
        converged=bool(res.converged),
        true_resnorm=float(res.true_resnorm),
        k=fac.k,
        info={
            "variant": fac.variant,
            "variant_requested": pl.opts.variant,
            "reduced_solver": fac.pc.reduced_solver,
            "d_factor": float(fac.d_factor),
            "p": pl.opts.p,
        },
    )


def solve_sparse(
    a_csr,
    b: np.ndarray,
    opts: Optional[SaPOptions] = None,
) -> SaPSolution:
    """One-shot solve of a sparse system via the reorder + banded pipeline.

    Deprecated for repeated solves: this re-runs DB/CM reordering and the
    block-LU factorization on every call.  Use ``factor(plan(a, opts))``
    and reuse the handle across right-hand sides.
    """
    _warn_one_shot("solve_sparse", "factor(plan(a, opts)).solve(b)")
    pl = plan(a_csr, opts)
    fac = factor(pl)
    res = fac.solve(jnp.asarray(np.asarray(b)))
    return SaPSolution(
        x=np.asarray(res.x),
        iterations=float(res.iterations),
        resnorm=float(res.resnorm),
        converged=bool(res.converged),
        true_resnorm=float(res.true_resnorm),
        k=fac.k,
        info={
            **pl.info,
            "variant": fac.variant,
            "variant_requested": pl.opts.variant,
            "reduced_solver": fac.pc.reduced_solver,
            "d_factor": float(fac.d_factor),
            "p": pl.opts.p,
        },
    )
