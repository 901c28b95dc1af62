"""A training cell run end to end on the CPU at a tiny size, skipping
only the harness's look for a chip: sound, it is correct under the
committed limits of ``booking-train``; with the timed path broken, or
with the control (the reference at float8 inputs) in the program's
place, it is not."""
from __future__ import annotations

import pytest

from chip import harness, run
from chip.tests import small

LIMITS = harness.load_json(harness.HERE, "workloads",
                           "booking-train.json")["limits"]


def _run(fault=None, seed=3):
    return run.run_cell("tiny-train", seed, 0.5, False, require_chip=False,
                        fault=fault,
                        cell_files=small.cell_files("train", LIMITS))


def test_sound_run_is_correct_and_reports_its_metrics():
    result, checks = _run(seed=2 ** 31 + 11)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_seq_per_s", "setup_s"}
    assert {n for n, _, _ in checks} == set(LIMITS)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(fault):
    result, checks = _run(fault)
    assert not result["correct"], checks


def test_control_fails_the_limits():
    from chip.drivers import train
    c = train.Cell(config=small.CONFIG, traffic=small.TRAIN, seed=5,
                   spans=harness.Spans())
    c.setup()
    c.free()
    numbers = c.compare(c.reference("fp8"), c.reference("f32"))
    assert any(numbers[k] > LIMITS[k] for k in LIMITS), numbers
