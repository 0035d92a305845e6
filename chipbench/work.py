"""Work of the solver's stages, counted from the problem's shape alone.

The counts are of the algorithm the paper states (arXiv:1509.07919
Sec. 2.1), not of any kernel, so fusing or splitting kernels leaves them
alone.  They are least counts: a stage that does more work shows a lower
share of its roofline, never a share above 100%.

Peaks come only from ``peaks.json``, keyed by JAX's ``device_kind``; a
kind that is not there is an error.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
F32 = 4  # bytes per entry


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peak table's row for ``device_kind``; raises KeyError if absent."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def partition_rows(n: int, p: int) -> list[int]:
    """Rows per partition: the first N mod P partitions get one more."""
    base, rem = divmod(n, p)
    return [base + 1 if i < rem else base for i in range(p)]


def band_lu_flops(rows: int, k: int) -> int:
    """Multiplies, adds and divides of an LU without pivoting of a banded
    (rows x rows) matrix of half bandwidth k: pivot i scales the r rows
    below it (r divides) and updates r x r entries (one multiply, one
    subtract each), r = min(k, rows - 1 - i)."""
    full = max(rows - k, 0)  # pivots with a whole band below and right
    tail = min(k, rows)
    flops = full * (k + 2 * k * k)
    for i in range(tail):  # the last pivots see a shrinking band
        r = tail - 1 - i
        flops += r + 2 * r * r
    return flops


def factor_stage(n: int, k: int, p: int, variant: str) -> dict:
    """Least flops and HBM bytes of the factor stage in float32.

    Every variant factors each partition's band by LU.  The coupled
    variant C also factors it by UL, and takes the bottom K x K tip of the
    right spike V_i from the LU and the top tip of the left spike W_i from
    the UL: each tip is a forward and a backward K x K triangular solve
    with K right-hand sides (K^3 flops each), at each of the P - 1
    interfaces.  The decoupled
    variant D needs the LU alone.  Bytes: the band is read once and the
    LU factors, which fill a band of the same shape, are written once.
    """
    rows = partition_rows(n, p)
    lu = sum(band_lu_flops(r, k) for r in rows)
    if variant == "D":
        flops = lu
    elif variant == "C":
        flops = 2 * lu + (p - 1) * 2 * 2 * k**3
    else:
        raise ValueError(f"no factor-stage count for variant {variant!r}")
    band_bytes = n * (2 * k + 1) * F32
    return {"flops": float(flops), "bytes": float(2 * band_bytes)}


def least_time(work: dict, peak: dict) -> tuple[float, str]:
    """Roofline floor of a float32 stage at matmul precision HIGHEST:
    (seconds, "compute" or "memory", whichever bounds it)."""
    compute = work["flops"] / peak["f32_highest_flops_per_s"]
    memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
