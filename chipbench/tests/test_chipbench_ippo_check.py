"""The ippo sweep cell, whole runs at a small size on the CPU: sound, control, faults.

Each run skips only the look for a chip.  A sound run is correct; the
control (the reference in bfloat16 in the program's place) and every
fault planted under the timed path come out not correct.
"""
import pytest

from chipbench_testing import run_small, small_cell, without_chip

import faults

CELL = "ippo_smax.sweep10x128"


@pytest.fixture
def cell(monkeypatch):
    with without_chip(monkeypatch):
        yield small_cell(CELL)


def test_sound_run_is_correct(cell):
    result = run_small(cell)
    assert result["correct"], result["checks"]
    assert result["metrics"]["train_steps_per_s"]["value"] > 0
    assert list(result)[-1] == "checks"


def test_control_is_not_correct(cell):
    import harness

    sweep = harness.runner("anakin_seeds").Sweep(cell, 11)
    sweep.setup()
    sweep.free()
    numbers = sweep.check(control=True)
    assert any(numbers[k] > v for k, v in cell.limits.items()), numbers


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_fault_is_not_correct(cell, monkeypatch, fault):
    import harness

    build = harness.build_system
    monkeypatch.setattr(harness, "build_system", lambda config: faults.TRAIN[fault](build(config)))
    result = run_small(cell)
    assert not result["correct"], result["checks"]
