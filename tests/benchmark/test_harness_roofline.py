"""The yardstick's arithmetic: the least bytes of a merge and the table
of peaks."""

import pytest

from benchmark import roofline


def test_least_bytes_counts_reads_and_writes():
    # two pairs: 10 + 12 nodes in, 15 merged; 3 + 3 in, 4 merged
    assert roofline.merge_least_bytes([(10, 12, 15), (3, 3, 4)]) == \
        (22 * 16 + 15 * 5) + (6 * 16 + 4 * 5)


def test_least_bytes_of_the_div1000_fleet():
    pair = (10_001, 10_001, 11_001)
    assert roofline.merge_least_bytes([pair] * 1024) == \
        1024 * (20_002 * 16 + 11_001 * 5)


def test_v5e_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes"] == 16e9


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("cpu")
