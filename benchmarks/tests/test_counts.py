"""The yardstick's arithmetic against hand calculations."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import counts  # noqa: E402


def test_iteration_bytes_at_a_toy_shape():
    # 3 users x 4 items, rank 2, bf16 factors; 7 edges kept by user, the cap
    # left 6 by item. An edge: 4 (index) + 4 (value) + 2 x 2 (factor row) = 12
    # bytes, read once in each orientation: 13 x 12 = 156. A row: a 2 x 2
    # Gram and a 2-vector in float32 = 24 bytes, 7 rows: 168.
    assert counts.als_iteration_bytes(7, 6, 3, 4, rank=2, factor_itemsize=2) == 324


def test_iteration_bytes_counts_edges_not_slots():
    # the count does not know how the rows were padded
    few = counts.als_iteration_bytes(10, 10, 5, 5, rank=16, factor_itemsize=4)
    more = counts.als_iteration_bytes(11, 10, 5, 5, rank=16, factor_itemsize=4)
    assert more - few == 4 + 4 + 16 * 4


def test_hbm_share_is_least_time_over_time_taken():
    # 819e9 bytes at 819 GB/s is one second; taken in four: 25%
    assert counts.hbm_share_pct(819e9, 4.0, "TPU v5 lite") == pytest.approx(25.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        counts.device_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        counts.hbm_share_pct(1.0, 1.0, "cpu")
