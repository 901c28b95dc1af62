"""Operation and byte counts against hand counts at stated shapes."""
from __future__ import annotations

import pytest

from chip import flops, harness
from chip.metrics import jpq_topk_pruned_roofline as roofline

BOOKING = harness.load_json(harness.HERE, "configs",
                            "sasrec-jpq-booking.json")


def test_encoder_position_by_hand():
    # d 512, 2 layers, d_ff 1024, S 200:
    # per layer 8*512^2 + 4*200*512 + 4*512*1024 = 4,603,904
    assert flops.encoder_position(BOOKING, 200) == 2 * 4_603_904


def test_lut_by_hand():
    assert flops.lut(BOOKING) == 2 * 256 * 512


def test_train_sequence_by_hand():
    rows = 34_742 + 2
    per_position = 3 * (9_207_808 + 262_144) + 2 * 8 * rows + 4 * rows
    assert flops.train_sequence(BOOKING) == 200 * per_position
    # about 29 MFLOP a position, as the issue reckoned
    assert flops.train_sequence(BOOKING) / 200 == pytest.approx(29.1e6,
                                                                rel=0.01)


def test_pruned_sweep_work_by_hand():
    # B 32, k 100, m 8, b 256 over 1,280,969 items, nothing skipped:
    # codes 1 byte each, the LUT once, values and ids out
    nbytes, ops = roofline.work(BOOKING, 100, 32, 1_280_969)
    assert nbytes == 1_280_969 * 8 + 32 * 8 * 256 * 4 + 32 * 100 * 8
    assert ops == 32 * 1_280_969 * 8
    # bytes bound it on a v5e: 10.5 MB at 819 GB/s against 0.33 GOP
    peaks = harness.peaks("TPU v5 lite")
    assert nbytes / peaks["hbm_bytes_per_s"] > ops / peaks["bf16_flops"]


def test_skipped_tiles_cost_nothing():
    nbytes, ops = roofline.work(BOOKING, 100, 32, 0)
    assert ops == 0 and nbytes == 32 * 8 * 256 * 4 + 32 * 100 * 8


def test_peaks_table_is_the_published_v5e_and_refuses_unknown_kinds():
    p = harness.peaks("TPU v5 lite")
    assert p == {"bf16_flops": 197e12, "int8_ops": 393e12,
                 "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
