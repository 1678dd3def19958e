"""The serving cell, whole runs at a small size on the CPU: sound, control, faults."""
import pytest

from chipbench_testing import run_small, small_cell, without_chip

import faults

CELL = "ippo_smax.serve_poisson"


@pytest.fixture
def cell(monkeypatch):
    with without_chip(monkeypatch):
        yield small_cell(CELL)


def test_sound_run_is_correct(cell):
    result = run_small(cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["serve_decisions_per_s"]["value"] > 0


def test_control_is_not_correct(cell):
    import harness

    server = harness.runner("serve_open_loop").Server(cell, 11, 1.0)
    server.serve(cell.traffic["drain_seconds"])
    numbers = server.check(control=True)
    assert any(numbers[k] > v for k, v in cell.limits.items()), numbers


@pytest.mark.parametrize("fault", sorted(faults.SERVE))
def test_fault_is_not_correct(cell, monkeypatch, fault):
    import harness

    build = harness.build_system
    monkeypatch.setattr(harness, "build_system", lambda config: faults.SERVE[fault](build(config)))
    result = run_small(cell)
    assert not result["correct"], result["checks"]
