"""Stage work counts and the peak table."""

import json

import pytest

from chipbench import work


def _counted_band_lu(rows: int, k: int) -> int:
    """Run an LU without pivoting of a full band, counting every multiply,
    add and divide it does."""
    flops = 0
    for i in range(rows):
        below = [r for r in range(i + 1, min(rows, i + k + 1))]
        right = [c for c in range(i + 1, min(rows, i + k + 1))]
        for _ in below:
            flops += 1  # the multiplier: one divide
            flops += 2 * len(right)  # a[r, c] -= l * a[i, c]
    return flops


@pytest.mark.parametrize("rows,k", [(1, 1), (5, 1), (7, 3), (12, 4), (3, 5), (40, 6)])
def test_band_lu_count_matches_counted_elimination(rows, k):
    assert work.band_lu_flops(rows, k) == _counted_band_lu(rows, k)


def test_factor_stage_hand_count():
    # N 10, K 2, P 3: partitions of 4, 3, 3 rows
    lu = _counted_band_lu(4, 2) + 2 * _counted_band_lu(3, 2)
    # 2 interfaces x 2 tips x (forward + backward K x K solve, K^3 flops each)
    tips = 2 * 2 * 2 * 2**3
    c = work.factor_stage(10, 2, 3, "C")
    assert c["flops"] == 2 * lu + tips
    assert c["bytes"] == 2 * 10 * 5 * 4
    assert work.factor_stage(10, 2, 3, "D")["flops"] == lu
    with pytest.raises(ValueError):
        work.factor_stage(10, 2, 3, "E")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_v5e_peaks_and_bound():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["f32_highest_flops_per_s"] == pytest.approx(197e12 / 6)
    assert json.loads(work.PEAKS_FILE.read_text())["TPU v5 lite"]["source"]
    # the paper's dense size is bound by compute
    t, bound = work.least_time(work.factor_stage(200_000, 200, 16, "C"), p)
    assert bound == "compute" and 5e-4 < t < 2e-3
    # a stage with no flops is bound by memory
    assert work.least_time({"flops": 0.0, "bytes": 819e9}, p) == (1.0, "memory")
