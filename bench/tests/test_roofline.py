import pytest

from bench import roofline


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_v5e_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_gather_distance_cost_by_hand():
    # B=4096 queries, 60 candidates each, 128-wide fp32 rows
    ops, nbytes = roofline.gather_distance_cost(4096, 60, 128)
    assert ops == 4096 * 60 * (2 * 128 + 4)
    rows = 4096 * 60 * 128 * 4  # 125,829,120 bytes of candidate rows
    assert nbytes == rows + 4096 * 128 * 4 + 4096 * 60 * 16


def test_share_computed_by_hand():
    ops, nbytes = roofline.gather_distance_cost(4096, 60, 128)
    # memory bound: 131.86 MB at 819 GB/s is 161.0 us; in 10 ms that is 1.610 %
    share, bound = roofline.roofline_share(ops, nbytes, 0.010, "TPU v5 lite")
    assert bound == "memory"
    assert share == pytest.approx(100 * nbytes / 819e9 / 0.010)
    assert share == pytest.approx(1.6100, abs=1e-3)


def test_compute_bound_share():
    # 1e12 operations and no bytes in 1 s: 6 bf16 passes at 197e12 per s
    share, bound = roofline.roofline_share(1e12, 0.0, 1.0, "TPU v5 lite")
    assert bound == "compute"
    assert share == pytest.approx(100 * 6e12 / 197e12)
