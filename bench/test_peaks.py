"""The peak table and the roofline arithmetic on hand-counted inputs."""
import pytest

from bench import peaks


def test_bandwidth_bound_share():
    # 819e9 bytes at 819 GB/s take 1 s; taking 2 s is 50% of the roofline
    assert peaks.roofline_share("TPU v5 lite", 0.0, 819e9, 2.0) == \
        pytest.approx(50.0)


def test_compute_bound_share():
    # 197e12 bf16 flops take 1 s; with 1 byte moved, compute bounds it
    assert peaks.roofline_share("TPU v5 lite", 197e12, 1.0, 4.0) == \
        pytest.approx(25.0)


def test_larger_bound_wins():
    # 1e12 flops (5.08 ms) vs 8.19e9 bytes (10 ms): bytes bound it
    assert peaks.roofline_share("TPU v5 lite", 1e12, 8.19e9, 0.02) == \
        pytest.approx(50.0)


def test_unknown_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.for_kind("cpu")
