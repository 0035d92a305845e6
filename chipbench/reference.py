"""The plain reference: what A x = b means, in float64 on the host.

An answer x of the system A x = b is judged by its relative residual
||b - A x|| / ||b||, computed here in float64 from the float32 entries
the solver was given.  Nothing here imports the solver.
"""

from __future__ import annotations

import numpy as np


def band_matvec(band, x) -> np.ndarray:
    """A @ x in float64; A in (N, 2K+1) band storage, x (N, R).

    Row r of the band holds A[r, r - K .. r + K].  Each block of K rows
    meets a (K, 3K) window of the band, laid out by padding each row to
    3K + 1 entries and reading it back with row stride 3K, which shifts
    row o of a block by o; one batched matrix product then does the work.
    """
    band = np.asarray(band)
    n, w = band.shape
    k = (w - 1) // 2
    x = np.asarray(x, np.float64)
    if k == 0:
        return band[:, :1].astype(np.float64) * x
    nb = -(-n // k)
    pad = nb * k - n
    rows = np.zeros((nb * k, 3 * k + 1))
    rows[:n, :w] = band
    win = rows.reshape(nb, k * (3 * k + 1))[:, : 3 * k * k].reshape(nb, k, 3 * k)
    xp = np.zeros((nb * k + 2 * k, x.shape[1]))
    xp[k : k + n] = x
    xb = xp.reshape(nb + 2, k, -1)
    xw = np.concatenate([xb[:-2], xb[1:-1], xb[2:]], axis=1)  # (nb, 3K, R)
    return np.matmul(win, xw).reshape(nb * k, -1)[:n]


def residual(band, x, b) -> np.ndarray:
    """Per-column ||b - A x|| / ||b|| in float64; A in (N, 2K+1) band storage.

    ``x`` and ``b`` are (N,) or (N, R); the result has one entry per column.
    """
    n = np.shape(band)[0]
    x = np.asarray(x, np.float64).reshape(n, -1)
    b = np.asarray(b, np.float64).reshape(n, -1)
    return np.linalg.norm(b - band_matvec(band, x), axis=0) / np.linalg.norm(b, axis=0)
