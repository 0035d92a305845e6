"""Batched many-systems solves: one factorization/solve over a fleet axis.

The lifecycle API (:mod:`repro.core.sap`) amortizes the expensive stages
across right-hand sides of a *single* matrix.  The paper's target
workload, though, is sequences of moderately sized banded systems -- one
per time step, one per scenario, one per user -- and serving such fleets
wants a *system* batch axis: factor S independent systems in one vmapped
device pass and solve them in one compiled executable, instead of S
python-loop round trips.

Two layers live here:

1. **Batched lifecycle** -- :func:`batch_plan` / :func:`batch_factor`
   produce a :class:`BatchedSaPFactorization`: a stacked
   :class:`~repro.core.sap.SaPFactorization` pytree whose data leaves
   carry a leading system axis (built by vmapping the device stages of
   ``sap.factor``), with ``solve_batch`` (one RHS per system, ``(S, N)``)
   and ``solve_batch_many`` (``(S, N, R)``).

2. **Bucketing** -- heterogeneous fleets cannot share a compiled shape.
   :func:`bucket_shape` / :func:`bucket_by_shape` round each system's
   ``(N, K)`` up to a shared bucket (power-of-two rounding by default) and
   :func:`pad_band_to` embeds a system *exactly* into the bucket shape.

   The N axis pads with decoupled identity rows.  The K axis is the
   subtle one: zero side columns are *algebraically* exact but
   *structurally* singular -- a K' > K band whose outer diagonals are
   exactly zero has strictly-triangular coupling blocks, so the K'-blocked
   pivots of the block LU become ill-conditioned and the "exact" variant E
   preconditioner silently loses digits (the converged-but-wrong failure
   of ROADMAP/PR 6).  When K widens, :func:`pad_band_to` therefore
   *interleaves* identity rows instead: every K original rows are followed
   by K' - K identity slots, which makes the padded matrix a symmetric
   permutation of ``blkdiag(A, I)`` whose K'-blocked pivots are exactly
   ``(original KxK pivot) (+) I`` -- same conditioning as the unpadded
   factorization, bit-for-bit.  The row permutation
   (:func:`pad_permutation`) rides the factorization's ``b_perm`` /
   ``x_perm`` slots, so callers keep the contiguous contract: RHS in as
   ``[b; 0]``, solution out as ``[x; 0]``.

The per-system factorizations inside a batch are slicable
(:func:`index_factorization`) and re-stackable
(:func:`stack_factorizations`), which is what the serving engine
(:mod:`repro.serve.solver_engine`) uses to mix cached and freshly
factored systems inside one batched solve.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from functools import lru_cache, partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.cost import timed_compile
from ..obs.trace import span
from .banded import band_to_block_tridiag, diag_dominance_factor
from .operators import BandedOperator
from .sap import (
    SaPFactorization,
    SaPOptions,
    SaPSolveResult,
    _convergence_summary,
    _precond_dtype,
    _solve_impl,
    resolve_solver,
    resolve_variant,
)
from .spike import build_preconditioner


# ---------------------------------------------------------------------------
# Bucketing: shared compiled shapes for heterogeneous fleets
# ---------------------------------------------------------------------------


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _next_pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


def interleaved_rows(n: int, k: int, k_pad: int) -> int:
    """Rows the structurally exact K-widened embedding needs.

    Widening K to K' > K interleaves K' - K identity rows after every K
    original rows (see :func:`pad_band_to`), so N grows to
    ``ceil(N / K) * K'``.  No widening (or K = 0, where there are no
    couplings to keep well-conditioned) needs no extra rows.
    """
    if k <= 0 or k_pad <= k:
        return n
    return -(-n // k) * k_pad


def bucket_shape(
    n: int, k: int, p: int, rounding: str = "pow2"
) -> Tuple[int, int, int]:
    """Round a system's ``(N, K)`` up to its bucket ``(N', K', P)``.

    ``rounding="pow2"`` keeps the number of distinct compiled shapes
    logarithmic in the size spread (at most ~2x padding waste);
    ``"exact"`` buckets only identical shapes together.  ``K'`` is never
    rounded below 2 so degenerate K=0/1 systems still form K x K blocks.
    When ``K' > K`` the bucket's ``N'`` also covers the interleaved
    identity-row embedding (:func:`interleaved_rows`) so the K-widening
    stays structurally exact.
    """
    if rounding == "pow2":
        kb = max(_next_pow2(k), 2)
    elif rounding == "exact":
        kb = max(k, 2)
    else:
        raise ValueError(f"unknown bucket rounding {rounding!r}")
    n_eff = interleaved_rows(n, k, kb)
    if rounding == "pow2":
        nb = max(_next_pow2(n_eff), p * kb)
    else:
        nb = max(n_eff, p * kb)
    # block-tridiag partitioning pads to P * M * K' anyway; absorb that
    # padding into the bucket so the bucket key IS the compiled shape.
    nb = _round_up(nb, p * kb)
    return nb, kb, p


def bucket_by_shape(
    shapes: Sequence[Tuple[int, int]], p: int, rounding: str = "pow2"
) -> dict:
    """Group systems by shared compiled shape.

    ``shapes`` is a sequence of per-system ``(N, K)``; returns an ordered
    ``{(N', K', P): [indices...]}`` mapping (insertion order = first
    occurrence, so callers can drain buckets deterministically).
    """
    buckets: dict = {}
    for i, (n, k) in enumerate(shapes):
        buckets.setdefault(bucket_shape(n, k, p, rounding), []).append(i)
    return buckets


def _pad_positions(n: int, k: int, k_pad: int) -> np.ndarray:
    """Interleaved position of original row t: chunk ``t // k`` of K rows
    starts at ``(t // k) * K'`` in the padded frame."""
    t = np.arange(n)
    return (t // k) * k_pad + (t % k)


def pad_permutation(
    n: int, k: int, n_pad: int, k_pad: int
) -> Optional[np.ndarray]:
    """Contiguous -> padded row map of the bucket embedding, or None.

    Returns ``perm`` (int32, length N') such that for a padded-frame
    vector ``v``, ``v[perm]`` is the contiguous-frame vector: original
    row ``t < N`` lives at padded row ``perm[t]``, identity pad slots
    occupy ``perm[N:]``.  None when the embedding is contiguous (no
    K-widening, K = 0, or not enough rows to interleave), i.e. original
    rows simply occupy the first N slots.
    """
    if k <= 0 or k_pad <= k or interleaved_rows(n, k, k_pad) > n_pad:
        return None
    pos = _pad_positions(n, k, k_pad)
    pad_slots = np.setdiff1d(np.arange(n_pad), pos)
    return np.concatenate([pos, pad_slots]).astype(np.int32)


def _pad_band_interleaved(
    band: jax.Array, n_pad: int, k_pad: int
) -> jax.Array:
    """K-widening embedding that preserves block conditioning exactly.

    Insert ``K' - K`` identity rows after every K original rows.  The
    resulting (N', 2K'+1) band is a symmetric permutation of
    ``blkdiag(A, I)``: every K'xK' partition block of the block-tridiag
    factorization is (an original KxK block) (+) (an identity slot), so
    pivots, spikes, and the reduced interface system have *identical*
    conditioning to the unpadded factorization -- unlike zero side
    columns, which make the widened coupling blocks strictly triangular
    (structurally singular) and poison the f32 block-pivot inverses.
    """
    band = jnp.asarray(band)
    n, w = band.shape
    k = (w - 1) // 2
    pos = _pad_positions(n, k, k_pad)
    t = np.arange(n)
    rows, cols, src_t, src_j = [], [], [], []
    for j in range(w):
        c = t + (j - k)
        valid = (c >= 0) & (c < n)
        tv = t[valid]
        # |pos[c] - pos[t]| <= K' for |c - t| <= K: same or adjacent chunk
        off = pos[c[valid]] - pos[tv]
        rows.append(pos[tv])
        cols.append(k_pad + off)
        src_t.append(tv)
        src_j.append(np.full(tv.shape, j))
    out = jnp.zeros((n_pad, 2 * k_pad + 1), band.dtype)
    out = out.at[:, k_pad].set(1.0)  # identity everywhere ...
    return out.at[np.concatenate(rows), np.concatenate(cols)].set(
        band[np.concatenate(src_t), np.concatenate(src_j)]
    )  # ... original entries overwrite their slots (targets are unique)


def pad_band_to(band: jax.Array, n_pad: int, k_pad: int) -> jax.Array:
    """Embed an (N, 2K+1) band exactly into bucket shape (N', 2K'+1).

    When K widens (``K' > K > 0``) and the bucket has room
    (``interleaved_rows(N, K, K') <= N'``, guaranteed for buckets from
    :func:`bucket_shape`), the embedding interleaves identity rows so the
    padded matrix is a symmetric permutation of ``blkdiag(A, I)`` --
    structurally exact, same conditioning as unpadded (see
    :func:`_pad_band_interleaved`); recover the row order with
    :func:`pad_permutation` (``batch_factor`` wires it into the
    factorization's ``b_perm`` / ``x_perm`` automatically).

    Otherwise the embedding is contiguous: zero side columns for the
    added diagonals, identity rows appended below.  That form is
    algebraically exact too, but a widened K leaves structurally singular
    coupling blocks whose boosted pivots degrade the preconditioner --
    only acceptable when K does not widen.
    """
    band = jnp.asarray(band)
    n, w = band.shape
    k = (w - 1) // 2
    if k_pad < k or n_pad < n:
        raise ValueError(
            f"bucket shape (N'={n_pad}, K'={k_pad}) smaller than system "
            f"(N={n}, K={k})"
        )
    if pad_permutation(n, k, n_pad, k_pad) is not None:
        return _pad_band_interleaved(band, n_pad, k_pad)
    if k_pad != k:
        side = jnp.zeros((n, k_pad - k), band.dtype)
        band = jnp.concatenate([side, band, side], axis=1)
    if n_pad != n:
        rows = jnp.zeros((n_pad - n, 2 * k_pad + 1), band.dtype)
        rows = rows.at[:, k_pad].set(1.0)
        band = jnp.concatenate([band, rows], axis=0)
    return band


def band_effective_k(band) -> int:
    """True half-bandwidth: stored K minus exactly-zero outer diagonals.

    A band *stored* wider than its couplings (e.g. a K=3 matrix in K=4
    storage) reproduces the structurally-singular zero-diagonal problem
    no matter how it is bucketed; trimming to the effective K first
    (:func:`trim_band_to_effective`) restores the exact embedding.  Host-
    side (numpy) -- used on the serving escalation path.
    """
    a = np.asarray(band)
    k = (a.shape[1] - 1) // 2
    ke = k
    while ke > 0 and not (np.any(a[:, k - ke]) or np.any(a[:, k + ke])):
        ke -= 1
    return ke


def trim_band_to_effective(band) -> np.ndarray:
    """Drop exactly-zero outer diagonal pairs from band storage."""
    a = np.asarray(band)
    k = (a.shape[1] - 1) // 2
    ke = band_effective_k(a)
    return a if ke == k else a[:, k - ke: k + ke + 1]


def pad_rhs_to(b: jax.Array, n_pad: int) -> jax.Array:
    """Zero-pad a (N,) or (N, R) right-hand side to the bucket length."""
    b = jnp.asarray(b)
    if b.shape[0] == n_pad:
        return b
    pad = jnp.zeros((n_pad - b.shape[0],) + b.shape[1:], b.dtype)
    return jnp.concatenate([b, pad], axis=0)


# ---------------------------------------------------------------------------
# Stage 1: batch_plan (stack a fleet into one bucket shape)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BatchedSaPPlan:
    """Host-side plan for a fleet of banded systems sharing one bucket.

    bands   : (S, N', 2K'+1) stacked (padded) band storage
    k, n    : bucket half-bandwidth K' and size N'
    orig_ns : per-system original sizes (for un-padding results)
    orig_ks : per-system original half-bandwidths (for the interleaved
              K-widening permutations; empty = assume no widening)
    opts    : solver options shared by the whole batch
    """

    bands: jax.Array
    k: int
    n: int
    orig_ns: Tuple[int, ...]
    opts: SaPOptions
    orig_ks: Tuple[int, ...] = ()

    @property
    def s(self) -> int:
        """Number of systems in the batch."""
        return self.bands.shape[0]


def batch_plan(
    bands: Sequence[jax.Array] | jax.Array,
    opts: Optional[SaPOptions] = None,
    rounding: str = "pow2",
) -> BatchedSaPPlan:
    """Plan a fleet of banded systems as ONE stacked, bucket-padded batch.

    ``bands`` is either an already-stacked (S, N, 2K+1) array (uniform
    fleet) or a sequence of per-system (N_i, 2K_i+1) bands (heterogeneous
    fleet).  All systems are padded to the single bucket covering the
    largest ``(N, K)`` in the fleet -- callers that want *multiple*
    compiled shapes split the fleet with :func:`bucket_by_shape` first
    (the serving engine does exactly that).
    """
    opts = opts or SaPOptions()
    if isinstance(bands, (jnp.ndarray, np.ndarray)) and np.ndim(bands) == 3:
        stacked = jnp.asarray(bands)
        s, n, w = stacked.shape
        k = (w - 1) // 2
        nb, kb, _ = bucket_shape(n, k, opts.p, rounding)
        orig_ns = (n,) * s
        if (nb, kb) != (n, k):
            stacked = jnp.stack([pad_band_to(bd, nb, kb) for bd in stacked])
        return BatchedSaPPlan(
            bands=stacked, k=kb, n=nb, orig_ns=orig_ns, opts=opts,
            orig_ks=(k,) * s,
        )

    bands = [jnp.asarray(bd) for bd in bands]
    if not bands:
        raise ValueError("batch_plan needs at least one system")
    shapes = [(bd.shape[0], (bd.shape[1] - 1) // 2) for bd in bands]
    nb = max(bucket_shape(n, k, opts.p, rounding)[0] for n, k in shapes)
    kb = max(bucket_shape(n, k, opts.p, rounding)[1] for n, k in shapes)
    # the fleet bucket's K' may exceed a member's own bucket K', widening
    # its interleaved embedding beyond its own N' -- grow N' to cover the
    # worst member so every embedding stays structurally exact.
    need = max(interleaved_rows(n, k, kb) for n, k in shapes)
    if rounding == "pow2":
        nb = max(nb, _next_pow2(need))
    else:
        nb = max(nb, need)
    nb = _round_up(nb, opts.p * kb)  # one bucket for the whole fleet
    stacked = jnp.stack([pad_band_to(bd, nb, kb) for bd in bands])
    return BatchedSaPPlan(
        bands=stacked,
        k=kb,
        n=nb,
        orig_ns=tuple(n for n, _ in shapes),
        opts=opts,
        orig_ks=tuple(k for _, k in shapes),
    )


# ---------------------------------------------------------------------------
# Stage 2: batch_factor (vmapped device stages; one compiled factor pass)
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("fac",),
    meta_fields=("s", "orig_ns"),
)
@dataclasses.dataclass(eq=False)
class BatchedSaPFactorization:
    """S independent SaP factorizations stacked over a leading system axis.

    ``fac`` is a :class:`~repro.core.sap.SaPFactorization` whose *data*
    leaves (band, preconditioner factors, d_factor) carry a leading
    ``(S, ...)`` axis while the meta fields (bucket shape, tolerances)
    are shared -- exactly the layout ``jax.vmap`` wants, so the whole
    batch solves inside one compiled executable.
    """

    fac: SaPFactorization
    s: int
    orig_ns: Tuple[int, ...]

    @property
    def n(self) -> int:
        """Padded per-system size shared by the whole batch."""
        return self.fac.n

    @property
    def k(self) -> int:
        """Padded half-bandwidth shared by the whole batch."""
        return self.fac.k

    @property
    def variant(self) -> str:
        """Resolved SaP variant shared by the whole batch."""
        return self.fac.variant

    def solve_batch(
        self, b: jax.Array, record_history: bool = False
    ) -> SaPSolveResult:
        """Solve system i against RHS i: b (S, N') -> x (S, N')."""
        b = jnp.asarray(b)
        if b.ndim != 2 or b.shape != (self.s, self.n):
            raise ValueError(
                f"solve_batch expects one RHS per system, shape "
                f"({self.s}, {self.n}); got {b.shape}"
            )
        with span(
            "krylov", s=self.s, n=self.n, k=self.k, variant=self.variant
        ) as sp:
            res = sp.sync(_solve_batch(self.fac, b, record_history=record_history))
        if sp:
            sp.annotate(convergence=_convergence_summary(res))
        return res

    def solve_batch_many(
        self, b: jax.Array, record_history: bool = False
    ) -> SaPSolveResult:
        """Solve R RHS per system: b (S, N', R) -> x (S, N', R)."""
        b = jnp.asarray(b)
        if b.ndim != 3 or b.shape[:2] != (self.s, self.n):
            raise ValueError(
                f"solve_batch_many expects shape ({self.s}, {self.n}, R); "
                f"got {b.shape}"
            )
        with span(
            "krylov",
            s=self.s,
            n=self.n,
            k=self.k,
            variant=self.variant,
            nrhs=int(b.shape[2]),
        ) as sp:
            res = sp.sync(
                _solve_batch_many(self.fac, b, record_history=record_history)
            )
        if sp:
            sp.annotate(convergence=_convergence_summary(res))
        return res


@partial(jax.jit, static_argnames=("record_history",))
def _solve_batch(
    fac: SaPFactorization, b: jax.Array, record_history: bool = False
) -> SaPSolveResult:
    # every data leaf of ``fac`` carries the system axis: plain vmap.
    return jax.vmap(lambda f, bi: _solve_impl(f, bi, record_history))(fac, b)


@partial(jax.jit, static_argnames=("record_history",))
def _solve_batch_many(
    fac: SaPFactorization, b: jax.Array, record_history: bool = False
) -> SaPSolveResult:
    inner_axes = SaPSolveResult(
        x=1, iterations=0, resnorm=0, converged=0, true_resnorm=0,
        d_factor=None,
        history=0 if record_history else None,
    )

    def one_system(f, bm):
        return jax.vmap(
            lambda bi: _solve_impl(f, bi, record_history),
            in_axes=1,
            out_axes=inner_axes,
        )(bm)

    return jax.vmap(one_system)(fac, b)


def _factor_key(opts: SaPOptions) -> tuple:
    """The options that actually reach the factor stages -- tolerances and
    Krylov knobs deliberately excluded so they never force a re-trace --
    plus the kernel implementation, which ``REPRO_KERNEL_IMPL`` can change
    between calls."""
    from ..kernels.ops import default_impl  # lazy: repro.kernels imports core

    return (
        opts.boost_eps,
        opts.precond_dtype,
        opts.reduced_solver,
        opts.fused_factor,
        default_impl(),
    )


@lru_cache(maxsize=64)
def _factor_stages_fn(k: int, p: int, variant: str, opts_key: tuple):
    """Jitted, vmapped device stages of ``sap.factor`` for one bucket shape.

    Cached per (bucket, variant, factor-relevant options) so the serving
    engine's repeated ``batch_factor`` calls hit the same traced
    executable instead of re-tracing every step.
    """
    boost_eps, precond_dtype, reduced_solver, fused, impl = opts_key
    pdt = _precond_dtype(SaPOptions(precond_dtype=precond_dtype))

    def stages(band):
        d_factor = diag_dominance_factor(band)
        bt = band_to_block_tridiag(band, max(k, 1), p)
        pc = build_preconditioner(
            bt,
            variant=variant,
            boost_eps=boost_eps,
            precond_dtype=pdt,
            impl=impl,
            reduced_solver=reduced_solver,
            fused=fused,
        )
        return pc, d_factor

    return jax.jit(jax.vmap(stages))


# AOT-compiled factor-stage executables, keyed by (bucket, variant, factor
# options, exact input aval).  One compile per key serves execution
# (batch_factor), the compile-telemetry counters, AND the cost observatory
# (repro.obs.cost reads flops/bytes off the same executable via
# cost_analysis() / as_text()) -- a jit-path re-trace would pay the
# compile twice.  Bounded like _factor_stages_fn; evicted executables
# simply recompile on next use.
_STAGES_EXEC: OrderedDict = OrderedDict()
_STAGES_EXEC_LOCK = threading.Lock()
_STAGES_EXEC_CAP = 64


def factor_stages_compiled(k: int, p: int, variant: str, opts_key: tuple,
                           bands_aval):
    """AOT-compiled vmapped factor stages for one exact batch shape.

    ``bands_aval`` is anything with ``.shape``/``.dtype`` for the stacked
    (S, N', 2K'+1) bands -- a concrete array or a
    ``jax.ShapeDtypeStruct``.  Compile misses are counted and spanned via
    :func:`repro.obs.cost.timed_compile` under the ``factor.batch``
    label.
    """
    akey = (tuple(bands_aval.shape), jnp.dtype(bands_aval.dtype).name)
    ckey = (k, p, variant, opts_key, akey)
    with _STAGES_EXEC_LOCK:
        hit = _STAGES_EXEC.get(ckey)
        if hit is not None:
            _STAGES_EXEC.move_to_end(ckey)
            return hit
    stages = _factor_stages_fn(k, p, variant, opts_key)
    struct = jax.ShapeDtypeStruct(tuple(bands_aval.shape),
                                  jnp.dtype(bands_aval.dtype))
    lowered = stages.lower(struct)
    with timed_compile(
        "factor.batch", bucket=f"{struct.shape[1]}x{k}", s=struct.shape[0]
    ):
        compiled = lowered.compile()
    with _STAGES_EXEC_LOCK:
        # a racing thread may have compiled the same key; first in wins
        hit = _STAGES_EXEC.setdefault(ckey, compiled)
        _STAGES_EXEC.move_to_end(ckey)
        while len(_STAGES_EXEC) > _STAGES_EXEC_CAP:
            _STAGES_EXEC.popitem(last=False)
        return hit


def _stacked_permutations(bpl: BatchedSaPPlan):
    """Per-system contiguous<->padded row maps as stacked (S, N') leaves.

    ``x_perm[i]`` gathers system i's padded-frame solution back to the
    contiguous frame; ``b_perm[i]`` (its inverse) scatters the contiguous
    ``[b; 0]`` RHS into the interleaved frame.  Always materialized --
    identity rows for members that need no interleaving -- so every
    factorization of a bucket shares one pytree structure and the serving
    cache can stack factorizations coming from different plans.
    """
    orig_ks = bpl.orig_ks or (bpl.k,) * bpl.s
    ident = np.arange(bpl.n, dtype=np.int32)
    xs, bs = [], []
    for n, k in zip(bpl.orig_ns, orig_ks):
        perm = pad_permutation(n, k, bpl.n, bpl.k)
        if perm is None:
            xs.append(ident)
            bs.append(ident)
        else:
            xs.append(perm)
            bs.append(np.argsort(perm).astype(np.int32))
    return jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(bs))


def batch_factor(bpl: BatchedSaPPlan) -> BatchedSaPFactorization:
    """Factor every system in the batch in one vmapped device pass.

    ``variant="auto"`` resolves once for the whole batch from the *worst*
    (minimum) degree of diagonal dominance, so a single compiled shape
    covers the batch: conservative -- any non-dominant member makes the
    batch use the exact reduced system "E".  (Identity padding rows are
    infinitely dominant and do not perturb the estimate.)
    """
    opts = bpl.opts
    variant = opts.variant
    if variant == "auto":
        d_all = jax.jit(jax.vmap(diag_dominance_factor))(bpl.bands)
        variant = resolve_variant("auto", float(jnp.min(d_all)))
    with span(
        "factor.batch", s=bpl.s, n=bpl.n, k=bpl.k, p=opts.p, variant=variant
    ) as sp:
        compiled = factor_stages_compiled(
            bpl.k, opts.p, variant, _factor_key(opts), bpl.bands
        )
        pcs, d_factors = compiled(jnp.asarray(bpl.bands))
        sp.sync(pcs)
    x_perm, b_perm = _stacked_permutations(bpl)
    fac = SaPFactorization(
        op=BandedOperator(band=bpl.bands, n=bpl.n, k=bpl.k),
        pc=pcs,
        b_perm=b_perm,
        x_perm=x_perm,
        n=bpl.n,
        k=bpl.k,
        tol=opts.tol,
        maxiter=opts.maxiter,
        use_cg=opts.use_cg,
        iter_dtype=opts.iter_dtype,
        solver=resolve_solver(opts.solver, opts.use_cg),
        d_factor=d_factors,
    )
    return BatchedSaPFactorization(fac=fac, s=bpl.s, orig_ns=bpl.orig_ns)


# ---------------------------------------------------------------------------
# Slicing / restacking (the serving engine's cache currency)
# ---------------------------------------------------------------------------


def index_factorization(bfac: BatchedSaPFactorization, i: int) -> SaPFactorization:
    """Extract system ``i`` as a standalone single-system factorization."""
    return jax.tree_util.tree_map(lambda x: x[i], bfac.fac)


def stack_factorizations(
    facs: Sequence[SaPFactorization], orig_ns: Optional[Sequence[int]] = None
) -> BatchedSaPFactorization:
    """Stack single-system factorizations (same bucket shape) into a batch.

    The inverse of :func:`index_factorization`; all handles must share
    their meta (bucket shape, variant, tolerances) -- i.e. come from the
    same bucket -- or the stack is ill-formed and this raises.
    """
    facs = list(facs)
    if not facs:
        raise ValueError("stack_factorizations needs at least one handle")
    treedefs = {jax.tree_util.tree_structure(f) for f in facs}
    if len(treedefs) != 1:
        raise ValueError(
            "cannot stack factorizations from different buckets/variants: "
            f"{len(treedefs)} distinct pytree structures"
        )
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *facs)
    ns = tuple(orig_ns) if orig_ns is not None else (facs[0].n,) * len(facs)
    return BatchedSaPFactorization(fac=stacked, s=len(facs), orig_ns=ns)


def unpad_solution(x: jax.Array, orig_ns: Sequence[int]) -> List[np.ndarray]:
    """Slice a padded (S, N') batch solution back to per-system lengths."""
    xs = np.asarray(x)
    return [xs[i, :n] for i, n in enumerate(orig_ns)]
