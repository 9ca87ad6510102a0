"""costs.py on shapes counted by hand."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import costs  # noqa: E402


def test_single_host_reads_four_columns():
    # t, pool, bytes, weight: 4 columns x 4 bytes x 14,528 events
    assert costs.analyzer_least_bytes(14528, 1, False) == 14528 * 16


def test_multi_host_reads_the_host_column():
    # + host: 5 columns x 4 bytes
    assert costs.analyzer_least_bytes(1000, 4, False) == 20000


def test_qos_reads_the_class_column():
    assert costs.analyzer_least_bytes(1000, 4, True) == 24000
    assert costs.analyzer_least_bytes(1000, 1, True) == 20000


def test_roofline_share_is_least_time_over_device_time():
    # 819,000 events x 20 B = 16.38 MB -> 20 us at 819 GB/s; over 2 ms: 1 %
    share = costs.roofline_share(819_000, 4, False, 2e-3, 819e9)
    assert share == pytest.approx(1.0, rel=1e-12)
