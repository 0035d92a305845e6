"""Banded-matrix storage utilities.

Three representations are used throughout SaP::TPU:

1. ``dense``        : plain (N, N) array (tests / tiny problems only).
2. ``band``         : the paper's "tall and thin" storage, shape (N, 2K+1)
                      with ``band[r, j] == A[r, r - K + j]``.  The diagonal
                      lives in column K (paper Sec. 3.1).
3. ``block-tridiag``: the TPU-native form.  Each of the P partitions is a
                      block-tridiagonal matrix with (K x K) blocks, which
                      turns the scalar "window sliding" GPU factorization of
                      the paper into a chain of MXU-friendly (K x K) matmuls.
                      Shapes: D (P, M, K, K) diagonal blocks,
                              E (P, M, K, K) sub-diagonal  (E[:, 0] unused),
                              F (P, M, K, K) super-diagonal (F[:, M-1] unused).

The partition coupling blocks of the paper (B_i super- / C_i sub-coupling,
each K x K) are extracted separately; they drive the spike computation.

All functions are pure JAX (jnp) unless explicitly numpy-only helpers for
test-matrix generation.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# dense <-> band conversions
# ---------------------------------------------------------------------------


def dense_to_band(a: jax.Array, k: int) -> jax.Array:
    """Convert a dense (N, N) banded matrix into (N, 2K+1) band storage."""
    n = a.shape[0]
    cols = jnp.arange(-k, k + 1)

    def row(r):
        idx = r + cols
        valid = (idx >= 0) & (idx < n)
        return jnp.where(valid, a[r, jnp.clip(idx, 0, n - 1)], 0.0)

    return jax.vmap(row)(jnp.arange(n))


def band_to_dense(band: jax.Array) -> jax.Array:
    """Inverse of :func:`dense_to_band`."""
    n, w = band.shape
    k = (w - 1) // 2
    out = jnp.zeros((n, n), band.dtype)
    rows = jnp.arange(n)
    for j in range(w):  # small loop over band width; unrolled at trace time
        cols = rows - k + j
        valid = (cols >= 0) & (cols < n)
        out = out.at[rows, jnp.clip(cols, 0, n - 1)].add(
            jnp.where(valid, band[:, j], 0.0)
        )
    return out


def _block_windows(band, k: int, xp=jnp):
    """(n, 2K+1) band rows, n a multiple of K -> (n/K, K, 3K) windows.

    Row r's window spans the three block columns br-1 .. br+1 of its block
    row br = r // K: band column j lands at window index o + j, o = r % K.
    Every row is padded to 3K+1 entries and read back with row stride 3K,
    so row o of a block row starts o entries later, which is that shift --
    pure pad/reshape/slice, no scatter.
    """
    n, w = band.shape
    skew = xp.pad(
        band.reshape(n // k, k, w), ((0, 0), (0, 0), (0, 3 * k + 1 - w))
    )
    win = skew.reshape(n // k, k * (3 * k + 1))[:, : 3 * k * k]
    return win.reshape(n // k, k, 3 * k)


def band_matvec(band: jax.Array, x: jax.Array) -> jax.Array:
    """y = A @ x with A in band storage.  x: (N,) or (N, R).

    Each block row of K rows multiplies its (K, 3K) window by the 3K
    entries of x under it (:func:`_block_windows`): one elementwise product
    and one reduction, where a shifted sum over the 2K+1 diagonals lowers
    to 2K+1 ops (a TPU compile of minutes at K = 200 inside a solve loop).
    Entries of the band that fall outside the matrix meet zero padding of x.
    """
    n, w = band.shape
    k = (w - 1) // 2
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    if k == 0:
        y = band * x
    else:
        nb = -(-n // k)
        pad = nb * k - n
        win = _block_windows(jnp.pad(band, ((0, pad), (0, 0))), k)
        xb = jnp.pad(x, ((k, pad + k), (0, 0))).reshape(nb + 2, k, -1)
        xw = jnp.concatenate([xb[:-2], xb[1:-1], xb[2:]], axis=1)  # (nb, 3K, R)
        y = jnp.sum(win[..., None] * xw[:, None], axis=2).reshape(nb * k, -1)[:n]
    return y[:, 0] if squeeze else y


def diag_dominance_factor(band: jax.Array) -> jax.Array:
    """Degree of diagonal dominance ``d`` of a band-storage matrix.

    Paper Eq. 2.11: the largest ``d`` such that ``|a_ii| >= d * sum_{j!=i}
    |a_ij|`` holds for every row, i.e. ``min_i |a_ii| / sum_{j!=i} |a_ij|``.
    Rows with no off-diagonal mass are infinitely dominant and drop out of
    the minimum (a pure diagonal matrix returns ``inf``).

    The paper's guidance (Sec. 2.1.1): spike truncation is justified for
    d >= 1 (variants C/D); below that the decay argument fails and the
    exact reduced system (variant "E") is the robust choice -- this scalar
    drives the ``variant="auto"`` policy in :mod:`repro.core.sap`.
    """
    w = band.shape[1]
    k = (w - 1) // 2
    diag = jnp.abs(band[:, k])
    off = jnp.sum(jnp.abs(band), axis=1) - diag
    safe = jnp.where(off > 0, off, 1.0)
    ratio = jnp.where(off > 0, diag / safe, jnp.inf)
    return jnp.min(ratio)


# ---------------------------------------------------------------------------
# Partitioning (paper Sec. 3.1: first P_r partitions get floor(N/P)+1 rows)
# ---------------------------------------------------------------------------


def partition_sizes(n: int, p: int) -> np.ndarray:
    base = n // p
    rem = n - p * base
    return np.asarray([base + 1 if i < rem else base for i in range(p)])


def padded_partition_size(n: int, p: int, k: int) -> int:
    """Uniform per-partition row count, padded so K | Ni (identity padding)."""
    ni = -(-n // p)  # ceil
    m = -(-ni // k)
    return m * k


def pad_banded(band: jax.Array, b: jax.Array, n_pad: int) -> Tuple[jax.Array, jax.Array]:
    """Pad system with identity rows so the total size becomes ``n_pad``."""
    n, w = band.shape
    k = (w - 1) // 2
    if n_pad == n:
        return band, b
    extra = n_pad - n
    pad_rows = jnp.zeros((extra, w), band.dtype).at[:, k].set(1.0)
    band_p = jnp.concatenate([band, pad_rows], axis=0)
    if b.ndim == 1:
        b_p = jnp.concatenate([b, jnp.zeros((extra,), b.dtype)])
    else:
        b_p = jnp.concatenate([b, jnp.zeros((extra, b.shape[1]), b.dtype)], axis=0)
    return band_p, b_p


# ---------------------------------------------------------------------------
# band -> block tridiagonal (per partition)
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("d", "e", "f", "b_cpl", "c_cpl"),
    meta_fields=("n",),
)
@dataclasses.dataclass
class BlockTridiag:
    """Block-tridiagonal form of the P partitions + coupling blocks.

    d: (P, M, K, K)   diagonal blocks
    e: (P, M, K, K)   sub-diagonal blocks   (e[:, 0] is zero / unused)
    f: (P, M, K, K)   super-diagonal blocks (f[:, M-1] is zero / unused)
    b_cpl: (P-1, K, K) super coupling block B_i  (rows: bottom of part i,
                        cols: top of part i+1)
    c_cpl: (P-1, K, K) sub coupling block C_{i+1} (rows: top of part i+1,
                        cols: bottom of part i)
    n: original (unpadded) system size
    """

    d: jax.Array
    e: jax.Array
    f: jax.Array
    b_cpl: jax.Array
    c_cpl: jax.Array
    n: int

    @property
    def p(self) -> int:
        return self.d.shape[0]

    @property
    def m(self) -> int:
        return self.d.shape[1]

    @property
    def k(self) -> int:
        return self.d.shape[2]

    @property
    def n_pad(self) -> int:
        return self.p * self.m * self.k

    def tree_flatten(self):  # pragma: no cover - convenience
        return (self.d, self.e, self.f, self.b_cpl, self.c_cpl), self.n


def band_to_block_tridiag(band, k: int, p: int, xp=jnp) -> BlockTridiag:
    """Split a banded system into P partitions of block-tridiagonal (K x K).

    ``xp`` is the array module: ``jnp`` (default; traceable under jit and
    vmap) or ``np``, which builds the blocks in host memory -- the sharded
    path places them on the mesh from there, so no device ever holds the
    whole system.  The split is pure data movement (:func:`_block_windows`).
    """
    band = xp.asarray(band)
    n, w = band.shape
    ni = padded_partition_size(n, p, k)
    n_pad = ni * p
    m = ni // k
    if n_pad > n:  # identity rows up to the padded size
        eye_row = (xp.arange(w) == (w - 1) // 2).astype(band.dtype)
        band = xp.concatenate(
            [band, xp.broadcast_to(eye_row, (n_pad - n, w))], axis=0
        )

    # Entries that fall off the matrix's first or last block column only
    # reach blocks zeroed below.
    win = _block_windows(band, k, xp).reshape(p, m, k, 3 * k)
    e = win[:, :, :, 0:k]
    d = win[:, :, :, k : 2 * k]
    f = win[:, :, :, 2 * k : 3 * k]
    # Zero out the cross-partition pieces: block row 0's sub-diag and block
    # row M-1's super-diag belong to coupling blocks, not to the partition.
    row = xp.arange(m)[None, :, None, None]
    e = xp.where(row == 0, xp.zeros((), band.dtype), e)
    f = xp.where(row == m - 1, xp.zeros((), band.dtype), f)

    # Coupling blocks. B_i = A[part i bottom K rows, part i+1 top K cols]
    # which is exactly the F window of block row (i, M-1); C similarly.
    b_cpl = win[:-1, m - 1, :, 2 * k : 3 * k]  # (p-1, k, k)
    c_cpl = win[1:, 0, :, 0:k]  # (p-1, k, k)
    return BlockTridiag(d=d, e=e, f=f, b_cpl=b_cpl, c_cpl=c_cpl, n=n)


def block_tridiag_to_dense(bt: BlockTridiag) -> jax.Array:
    """Reassemble the full (padded) dense matrix (tests only)."""
    p, m, k = bt.p, bt.m, bt.k
    n = bt.n_pad
    out = np.zeros((n, n), dtype=np.asarray(bt.d).dtype)
    d, e, f = np.asarray(bt.d), np.asarray(bt.e), np.asarray(bt.f)
    for i in range(p):
        off = i * m * k
        for j in range(m):
            r0 = off + j * k
            out[r0 : r0 + k, r0 : r0 + k] = d[i, j]
            if j > 0:
                out[r0 : r0 + k, r0 - k : r0] = e[i, j]
            if j < m - 1:
                out[r0 : r0 + k, r0 + k : r0 + 2 * k] = f[i, j]
    b_cpl, c_cpl = np.asarray(bt.b_cpl), np.asarray(bt.c_cpl)
    for i in range(p - 1):
        rb = (i + 1) * m * k  # first row of partition i+1
        out[rb - k : rb, rb : rb + k] = b_cpl[i]
        out[rb : rb + k, rb - k : rb] = c_cpl[i]
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# Test-matrix generators (numpy; mirror the paper's experiments Sec. 4.1)
# ---------------------------------------------------------------------------


def random_banded(
    n: int,
    k: int,
    d: float,
    seed: int = 0,
    dtype=np.float64,
) -> np.ndarray:
    """Random band-storage matrix with degree of diagonal dominance ``d``.

    Off-diagonal entries are U(-1, 1); the diagonal is set so that
    |a_ii| = d * sum_{j != i} |a_ij|  (paper Eq. 2.11, with equality).
    Returns band storage (N, 2K+1).
    """
    rng = np.random.default_rng(seed)
    band = rng.uniform(-1.0, 1.0, size=(n, 2 * k + 1)).astype(dtype)
    # zero out-of-matrix corners
    for j in range(2 * k + 1):
        c = np.arange(n) - k + j
        band[(c < 0) | (c >= n), j] = 0.0
    off = np.abs(band).sum(axis=1) - np.abs(band[:, k])
    sign = np.where(band[:, k] >= 0, 1.0, -1.0)
    band[:, k] = sign * np.maximum(d * off, 1e-3)
    return band


def oscillatory_banded(
    n: int,
    k: int,
    d: float,
    jitter: float = 0.02,
    seed: int = 0,
    dtype=np.float64,
) -> np.ndarray:
    """Band-storage matrix with dominance ``d`` and *non-decaying* spikes.

    :func:`random_banded` draws off-diagonals from U(-1, 1); the random
    signs cancel, so even for d < 1 the partition inverses decay and the
    truncated SPIKE variants stay accurate.  Here every off-diagonal is
    coherently negative (-1 with a small positive jitter), which puts the
    symbol of the matrix near zero: the characteristic roots sit on the
    unit circle and the spikes oscillate without decaying.  For d < 1 this
    is the regime where truncation (variants C/D) genuinely breaks down
    and the exact reduced system (variant "E") is required -- the hard
    scenario of paper Sec. 2.1/4.1.  Returns band storage (N, 2K+1).
    """
    rng = np.random.default_rng(seed)
    band = -(1.0 + jitter * rng.uniform(0.0, 1.0, size=(n, 2 * k + 1)))
    band = band.astype(dtype)
    for j in range(2 * k + 1):
        c = np.arange(n) - k + j
        band[(c < 0) | (c >= n), j] = 0.0
    off = np.abs(band).sum(axis=1) - np.abs(band[:, k])
    band[:, k] = np.maximum(d * off, 1e-3)
    return band


def random_rhs(n: int, seed: int = 1, dtype=np.float64) -> np.ndarray:
    """Paper Sec 4.3.3: entries on a parabola from 1.0 to ~400 back to 1.0."""
    t = np.linspace(-1.0, 1.0, n)
    return (400.0 * (1.0 - t * t) + 1.0).astype(dtype)
