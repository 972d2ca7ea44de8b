"""The bytes behind engine_roofline_pct, counted by hand, and the peaks
table."""
from bench_paths import BENCH  # noqa: F401  (sets the import path)

import numpy as np
import pytest

import roofline
from ops import ADD, ADDP, CADD, NOP, READ, WRITE


def test_work_bytes_of_four_packets_counted_by_hand():
    # 4 txns, K = 3: 1 + 3 + 2 + 2 = 8 instructions over slots
    # {10, 11, 12, 13, 20}: 5 distinct
    op = np.array([[READ, NOP, NOP],
                   [READ, WRITE, ADDP],
                   [CADD, ADD, NOP],
                   [READ, CADD, NOP]])
    slot = np.array([[10, 0, 0],
                     [11, 11, 12],
                     [13, 10, 0],
                     [20, 12, 99]])
    # per instruction 4 int32 in + 1 out = 20 B; per slot 4 B read + 4 B
    # written = 8 B
    assert roofline.work_bytes(op, slot) == 8 * 20 + 5 * 8


def test_window_bytes_counts_only_the_ops_on_hot_keys_per_batch():
    arrs = dict(op=np.array([[READ, WRITE], [READ, READ], [WRITE, NOP]]),
                key=np.array([[1, 2], [1, 7], [2, 0]]))
    hot_op = np.array([[True, True], [True, False], [True, False]])
    # batch 1 = txns 0, 1: 3 hot instructions on slots {1, 2}; batch 2 =
    # txn 2: 1 instruction on slot {2}
    got = roofline.window_bytes(arrs, hot_op, [(0, 2), (2, 3)])
    assert got == (3 * 20 + 2 * 8) + (1 * 20 + 1 * 8)


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
