"""Multi-device SaP: partition-per-device solver via shard_map.

The paper's P-way work splitting maps 1:1 onto the TPU mesh: every device
owns ``p_per_device`` partitions; factorization and the two block solves
of the preconditioner are embarrassingly parallel, and communication in
the preconditioner is nearest-neighbor or log-depth:

  variant C (truncated, Sec. 2.1):
    setup:  one ppermute of the left-spike top blocks  W^(t)   (K x K each)
    apply:  one ppermute of g^(t) (down) + one of xt^(b) (up)  (K x R each)
  variant E (exact reduced system, Sec. 2.1.1):
    setup:  one ppermute aligning spike corners + ~log2(P) strided shift
            rounds reducing the (P-1)-interface chain by parallel cyclic
            reduction (``repro.core.cyclic_reduction.pcr_factor``)
    apply:  ~log2(P) shift rounds of (2K x R) blocks -- the chain is
            *never* gathered onto one device.

i.e. O(K^2 log P) bytes per device per apply, independent of N -- the TPU
analogue of the paper's observation that the reduced system is tiny, now
extended to the exact coupling that stays robust below diagonal dominance
d = 1.  The banded matvec for the outer Krylov iteration needs a K-row
halo exchange (two ppermutes).  Everything else (dots, norms in BiCGStab)
is left to pjit/GSPMD at the top level.

Partitions are flattened over *all* mesh axes (tuple-axis collectives), so
the same code runs on the (data, model) single-pod mesh and the
(pod, data, model) multi-pod mesh -- partition boundaries crossing the pod
axis prove the pod-level sharding in the dry-run.

``variant="auto"`` applies the same C-vs-E policy as ``sap.factor()``:
the degree of diagonal dominance (Eq. 2.11) is estimated from shard-local
band rows and reduced over the mesh, picking C at d >= 1 and E below.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .banded import band_to_block_tridiag, diag_dominance_factor
from .block_lu import DEFAULT_BOOST, btf_ref, btf_ul_ref, bts_ref, gj_inverse, mm
from .cyclic_reduction import pcr_factor, pcr_n_levels, pcr_solve
from .krylov import bicgstab2
from .sap import SaPSolveResult, resolve_variant


def mesh_axes(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def n_devices(mesh) -> int:
    return int(mesh.devices.size)


# ---------------------------------------------------------------------------
# Neighbor shifts over the flattened mesh axes (non-cyclic: edges get zeros)
# ---------------------------------------------------------------------------


def _shift_from_next(x, axes):
    """Each device receives the value owned by device (idx+1); last gets 0."""
    n = jax.lax.axis_size(axes)
    perm = [(i + 1, i) for i in range(n - 1)]
    return jax.lax.ppermute(x, axes, perm)


def _shift_from_prev(x, axes):
    """Each device receives the value owned by device (idx-1); first gets 0."""
    n = jax.lax.axis_size(axes)
    perm = [(i, i + 1) for i in range(n - 1)]
    return jax.lax.ppermute(x, axes, perm)


def _from_prev_by(x, dq, axes):
    """Receive the block owned by the device ``dq`` positions before."""
    if dq == 0:
        return x
    n = jax.lax.axis_size(axes)
    perm = [(i, i + dq) for i in range(n - dq)]
    return jax.lax.ppermute(x, axes, perm)


def _from_next_by(x, dq, axes):
    if dq == 0:
        return x
    n = jax.lax.axis_size(axes)
    perm = [(i, i - dq) for i in range(dq, n)]
    return jax.lax.ppermute(x, axes, perm)


def _shift_dn_rows(x, s, axes):
    """Row j of the global (flattened, p_loc rows/device) array receives
    row j - s; rows shifted in past the start are zero.  One stride-s PCR
    neighbor exchange: at most two ppermutes regardless of s."""
    p_loc = x.shape[0]
    q, r = divmod(s, p_loc)
    a = _from_prev_by(x, q, axes)
    if r == 0:
        return a
    b = _from_prev_by(x, q + 1, axes)
    return jnp.concatenate([b[p_loc - r:], a[: p_loc - r]], axis=0)


def _shift_up_rows(x, s, axes):
    """Row j receives row j + s (zeros past the end)."""
    p_loc = x.shape[0]
    q, r = divmod(s, p_loc)
    a = _from_next_by(x, q, axes)
    if r == 0:
        return a
    b = _from_next_by(x, q + 1, axes)
    return jnp.concatenate([a[r:], b[:r]], axis=0)


def _flat_device_index(axes):
    """Row-major flattened index of this device over the mesh axes."""
    idx = jnp.int32(0)
    for ax in axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


# ---------------------------------------------------------------------------
# Distributed preconditioner
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DistSaP:
    """Compiled distributed solver handle."""

    mesh: object
    k: int
    m: int
    p_local: int
    n_pad: int
    variant: str  # resolved: "C" | "D" | "E"
    variant_requested: str
    matvec: callable
    precond: callable
    factor: callable
    shard_band: callable
    d_factor: Optional[float] = None  # Eq. 2.11 estimate ("auto" only)


def _local_factor_c(d, e, f, b_next, c_prev, boost_eps, axes):
    """Runs per device.  d/e/f: (p_loc, M, K, K); couplings per partition."""
    lu = btf_ref(d, e, f, boost_eps)
    # right-spike bottoms (for interface owned by this partition)
    v_bot = mm(lu.sinv[:, -1], b_next)  # (p_loc, K, K)
    # left-spike tops of *this* partition (for the interface owned by prev)
    ul = btf_ul_ref(d, e, f, boost_eps)
    w_top = mm(ul.sinv[:, -1], c_prev[..., ::-1, :])[..., ::-1, :]
    # align W^(t) of partition i+1 at interface index i
    w_next = jnp.concatenate(
        [w_top[1:], _shift_from_next(w_top[:1], axes)], axis=0
    )
    eye = jnp.eye(d.shape[-1], dtype=d.dtype)
    rbar = eye - mm(w_next, v_bot)
    rbar_inv = jax.vmap(lambda a: gj_inverse(a, boost_eps))(rbar)
    return lu, v_bot, w_next, rbar_inv


def _local_apply_c(state, b_next, c_prev, rb, axes):
    """Per-device truncated-coupling apply.  rb: (p_loc, M, K, R)."""
    lu, v_bot, w_next, rbar_inv = state
    g = bts_ref(lu, rb)
    g_top, g_bot = g[:, 0], g[:, -1]  # (p_loc, K, R)
    # g^(t) of partition i+1 aligned at interface i
    g_top_next = jnp.concatenate(
        [g_top[1:], _shift_from_next(g_top[:1], axes)], axis=0
    )
    rhs = g_top_next - mm(w_next, g_bot)
    xt_top = mm(rbar_inv, rhs)  # x~ for top of partition i+1
    xt_bot = g_bot - mm(v_bot, xt_top)  # x~ for bottom of partition i
    # partition j needs: bottom corr B_j xt_top[j] (local); top corr
    # C_j xt_bot[j-1] (shift up)
    xt_bot_prev = jnp.concatenate(
        [_shift_from_prev(xt_bot[-1:], axes), xt_bot[:-1]], axis=0
    )
    rb2 = rb.at[:, -1].add(-mm(b_next, xt_top))
    rb2 = rb2.at[:, 0].add(-mm(c_prev, xt_bot_prev))
    return bts_ref(lu, rb2)


def _local_factor_e(d, e, f, b_next, c_prev, boost_eps, axes, p_total):
    """Sharded exact coupling: assemble this device's (2K x 2K) interface
    blocks from whole-spike corners, then reduce the global chain by
    parallel cyclic reduction -- log2(P) strided shift rounds, no gather.
    """
    lu = btf_ref(d, e, f, boost_eps)
    p_loc, m, k, _ = d.shape
    dtype = d.dtype

    # whole spikes of the local partitions: A_j V_j = [0;..;B_j] (right),
    # A_j W_j = [C_j;0;..] (left); keep the four corner blocks.
    rhs_b = jnp.zeros((p_loc, m, k, k), dtype).at[:, -1].set(b_next)
    v = bts_ref(lu, rhs_b)
    rv_top, rv_bot = v[:, 0], v[:, -1]
    rhs_c = jnp.zeros((p_loc, m, k, k), dtype).at[:, 0].set(c_prev)
    w = bts_ref(lu, rhs_c)
    lw_top, lw_bot = w[:, 0], w[:, -1]

    # interface i lives with partition i and couples y_i = [x_i^b;
    # x_{i+1}^t]: it needs W_{i+1}^t / V_{i+1}^t from partition i+1.
    nxt = lambda x: jnp.concatenate(
        [x[1:], _shift_from_next(x[:1], axes)], axis=0
    )
    lw_top_next = nxt(lw_top)
    rv_top_next = nxt(rv_top)

    eye = jnp.broadcast_to(jnp.eye(k, dtype=dtype), (p_loc, k, k))
    zero = jnp.zeros((p_loc, k, k), dtype)

    def blk2(tl, tr, bl, br):
        top = jnp.concatenate([tl, tr], axis=-1)
        bot = jnp.concatenate([bl, br], axis=-1)
        return jnp.concatenate([top, bot], axis=-2)

    rd = blk2(eye, rv_bot, lw_top_next, eye)
    re = blk2(lw_bot, zero, zero, zero)  # couples to y_{i-1} via W_i^(b)
    rf = blk2(zero, zero, zero, rv_top_next)  # to y_{i+1} via V_{i+1}^(t)

    # The flattened chain has one slot per partition; the last partition's
    # slot is not a real interface -- pad it to a decoupled identity block.
    gidx = _flat_device_index(axes) * p_loc + jnp.arange(p_loc)
    is_pad = (gidx >= p_total - 1)[:, None, None]
    eye2 = jnp.broadcast_to(jnp.eye(2 * k, dtype=dtype), rd.shape)
    rd = jnp.where(is_pad, eye2, rd)
    re = jnp.where(is_pad, 0.0, re)
    rf = jnp.where(is_pad, 0.0, rf)

    shift_dn = lambda x, s: _shift_dn_rows(x, s, axes)
    shift_up = lambda x, s: _shift_up_rows(x, s, axes)
    pcr = pcr_factor(
        rd, re, rf, pcr_n_levels(p_total - 1),
        shift_dn=shift_dn, shift_up=shift_up, boost_eps=boost_eps,
    )
    return lu, pcr


def _local_apply_e(state, b_next, c_prev, rb, axes):
    """Exact-coupling apply: block solve + log-depth reduced sweep +
    corrected block solve (the sharded counterpart of spike._apply_exact)."""
    lu, pcr = state
    k = rb.shape[2]
    g = bts_ref(lu, rb)
    g_top, g_bot = g[:, 0], g[:, -1]  # (p_loc, K, R)
    g_top_next = jnp.concatenate(
        [g_top[1:], _shift_from_next(g_top[:1], axes)], axis=0
    )
    h = jnp.concatenate([g_bot, g_top_next], axis=1)  # (p_loc, 2K, R)
    y = pcr_solve(
        pcr, h,
        shift_dn=lambda x, s: _shift_dn_rows(x, s, axes),
        shift_up=lambda x, s: _shift_up_rows(x, s, axes),
    )
    xt_bot, xt_top = y[:, :k], y[:, k:]  # x_i^(b), x_{i+1}^(t)
    xt_bot_prev = jnp.concatenate(
        [_shift_from_prev(xt_bot[-1:], axes), xt_bot[:-1]], axis=0
    )
    rb2 = rb.at[:, -1].add(-mm(b_next, xt_top))
    rb2 = rb2.at[:, 0].add(-mm(c_prev, xt_bot_prev))
    return bts_ref(lu, rb2)


def _local_matvec(band_loc, x_loc, k, axes):
    """Banded matvec with K-row halo exchange.  band_loc: (N_loc, 2K+1)."""
    lo = _shift_from_prev(x_loc[-k:], axes)  # prev device's last K entries
    hi = _shift_from_next(x_loc[:k], axes)  # next device's first K entries
    x_ext = jnp.concatenate([lo, x_loc, hi], axis=0)
    n_loc = x_loc.shape[0]
    cols = [band_loc[:, j] * jax.lax.dynamic_slice(x_ext, (j,), (n_loc,))
            for j in range(2 * k + 1)]
    return sum(cols)


# ---------------------------------------------------------------------------
# Sharded dominance estimate (drives variant="auto")
# ---------------------------------------------------------------------------


def dist_diag_dominance_factor(mesh, band_p: jax.Array) -> jax.Array:
    """Degree of diagonal dominance (Eq. 2.11) from shard-local band rows.

    Each device reduces its own rows with :func:`diag_dominance_factor`
    (identity padding rows drop out as infinitely dominant) and the
    per-shard minima are combined with one ``pmin`` over the mesh axes --
    no row ever leaves its device.
    """
    axes = mesh_axes(mesh)

    def local_d(rows):
        return jax.lax.pmin(diag_dominance_factor(rows), axes)

    fn = jax.shard_map(
        local_d,
        mesh=mesh,
        in_specs=(P(axes, None),),
        out_specs=P(),
        check_vma=False,
    )
    return fn(band_p)


# ---------------------------------------------------------------------------
# Builder
# ---------------------------------------------------------------------------


def _host_pad(band, n_pad: int) -> np.ndarray:
    """Identity-pad (N, 2K+1) band rows to ``n_pad`` rows in host memory,
    in the dtype JAX would give the band (f32 unless x64 is on)."""
    band = np.asarray(band)
    n, w = band.shape
    out = np.zeros((n_pad, w), jax.dtypes.canonicalize_dtype(band.dtype))
    out[:n] = band
    out[n:, (w - 1) // 2] = 1.0
    return out


def build_dist_sap(
    mesh,
    n: int,
    k: int,
    variant: str = "C",
    p_per_device: int = 1,
    boost_eps: float = DEFAULT_BOOST,
    precond_dtype=jnp.float32,
    band=None,
):
    """Construct the shard_mapped matvec/precond/factor closures.

    Returns a :class:`DistSaP`; all functions operate on globally-sharded
    arrays and can be jit/lowered on the production mesh.

    ``variant`` is one of "C" (truncated coupling), "D" (decoupled), "E"
    (exact reduced interface chain via distributed cyclic reduction) or
    "auto" -- the same policy as ``sap.factor()``: C when the band is
    diagonally dominant (d >= 1, Eq. 2.11), E below.  "auto" needs the
    band rows to estimate d, so pass ``band`` (host (N, 2K+1) storage);
    the estimate itself runs sharded (:func:`dist_diag_dominance_factor`).
    """
    if variant not in ("C", "D", "E", "auto"):
        raise ValueError(f"unknown distributed SaP variant {variant!r}")
    axes = mesh_axes(mesh)
    ndev = n_devices(mesh)
    p_total = ndev * p_per_device
    ni = -(-n // p_total)  # ceil rows per partition
    m = max(2, -(-ni // k))  # blocks per partition (>= 2 so top != bottom)
    n_pad = p_total * m * k

    variant_requested = variant
    d_factor = None
    part_spec = P(axes)  # flattened over all axes
    place = lambda x: jax.device_put(x, NamedSharding(mesh, part_spec))

    if variant == "auto":
        if band is None:
            raise ValueError(
                'variant="auto" needs the band rows to estimate diagonal '
                "dominance; pass band=(N, 2K+1) storage to build_dist_sap"
            )
        band_p = place(_host_pad(band, n_pad))
        with mesh:
            d_factor = float(dist_diag_dominance_factor(mesh, band_p))
        variant = resolve_variant("auto", d_factor)

    def shard_band(band, b):
        """Pad the system and split it into partition blocks in host memory
        (numpy), then place every array on the mesh sharded by partition:
        no device ever holds the whole band or the whole block arrays.
        (The dry-run passes ShapeDtypeStructs instead.)"""
        band_p = _host_pad(band, n_pad)
        b = np.asarray(b)
        b_p = np.zeros((n_pad,) + b.shape[1:], jax.dtypes.canonicalize_dtype(b.dtype))
        b_p[: b.shape[0]] = b
        bt = band_to_block_tridiag(band_p, k, p_total, xp=np)
        zero = np.zeros((1, k, k), bt.b_cpl.dtype)
        pdt = np.dtype(precond_dtype)
        parts = {
            "d": bt.d,
            "e": bt.e,
            "f": bt.f,
            "b_next": np.concatenate([bt.b_cpl, zero], axis=0),
            "c_prev": np.concatenate([zero, bt.c_cpl], axis=0),
        }
        parts = {name: place(x.astype(pdt)) for name, x in parts.items()}
        return place(band_p), place(b_p), parts

    # ---- shard_mapped closures ---------------------------------------------
    # Every variant's factor returns an opaque per-device state pytree and
    # apply consumes it, so the shard_map plumbing is variant-independent.
    if variant == "C":
        def fac_local(d, e, f, b_next, c_prev):
            return _local_factor_c(d, e, f, b_next, c_prev, boost_eps, axes)

        def apply_local(state, b_next, c_prev, rb):
            return _local_apply_c(state, b_next, c_prev, rb, axes)
    elif variant == "E":
        def fac_local(d, e, f, b_next, c_prev):
            return _local_factor_e(
                d, e, f, b_next, c_prev, boost_eps, axes, p_total
            )

        def apply_local(state, b_next, c_prev, rb):
            return _local_apply_e(state, b_next, c_prev, rb, axes)
    else:
        def fac_local(d, e, f, b_next, c_prev):
            return (btf_ref(d, e, f, boost_eps),)

        def apply_local(state, b_next, c_prev, rb):
            return bts_ref(state[0], rb)

    fac_fn = jax.shard_map(
        fac_local,
        mesh=mesh,
        in_specs=(part_spec,) * 5,
        out_specs=part_spec,
        check_vma=False,
    )

    apply_fn = jax.shard_map(
        apply_local,
        mesh=mesh,
        in_specs=(part_spec,) * 4,
        out_specs=part_spec,
        check_vma=False,
    )

    mv_fn = jax.shard_map(
        lambda band, x: _local_matvec(band, x, k, axes),
        mesh=mesh,
        in_specs=(part_spec, part_spec),
        out_specs=part_spec,
        check_vma=False,
    )

    return DistSaP(
        mesh=mesh,
        k=k,
        m=m,
        p_local=p_per_device,
        n_pad=n_pad,
        variant=variant,
        variant_requested=variant_requested,
        matvec=mv_fn,
        precond=apply_fn,
        factor=fac_fn,
        shard_band=shard_band,
        d_factor=d_factor,
    )


def solve_step_fn(dsap: DistSaP, tol: float = 1e-8, maxiter: int = 200):
    """Whole-solve function suitable for jit/lower on the production mesh.

    Inputs: band (N_pad, 2K+1) row-sharded, b (N_pad,) sharded, plus the
    block-tridiag partition arrays.  Returns a :class:`~repro.core.sap.
    SaPSolveResult` -- solution plus the convergence diagnostics
    (iterations / resnorm / converged, and the sharded d-estimate when
    the variant was resolved by "auto").
    """
    k, m = dsap.k, dsap.m
    d_factor = dsap.d_factor

    def step(band, b, d, e, f, b_next, c_prev):
        state = dsap.factor(d, e, f, b_next, c_prev)
        p_total = d.shape[0]

        def precond(r):
            rb = r.reshape(p_total, m, k, 1).astype(d.dtype)
            z = dsap.precond(state, b_next, c_prev, rb)
            return z.reshape(r.shape).astype(r.dtype)

        def matvec(x):
            return dsap.matvec(band, x)

        res = bicgstab2(matvec, b, precond=precond, tol=tol, maxiter=maxiter)
        return SaPSolveResult(
            x=res.x,
            iterations=res.iterations,
            resnorm=res.resnorm,
            converged=res.converged,
            true_resnorm=res.true_resnorm,
            d_factor=None if d_factor is None else jnp.asarray(d_factor),
        )

    return step
