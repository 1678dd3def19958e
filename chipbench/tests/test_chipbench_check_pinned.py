"""The training cells' check numbers at a fixed seed, pinned to the last digit.

A whole run of each training cell at ``run_small``'s size and seed on the
CPU.  The pinned numbers are those the runner's own (recurrent) IPPO check
gave before that check became ``reference/ppo_check.py``: a change to the
check's arithmetic, or to what the timed path produces, shows here.
"""
import pytest

from chipbench_testing import run_small, small_cell, without_chip

PINNED = {
    "ippo_smax.sweep10x128": {
        "env_gap": 5.960464477539063e-08, "act_gap": 0.00010657310485839844, "act_margin": 0.0,
        "grad1": 5.415530012514306e-05, "delta3": 0.00042808464693751833,
    },
    "rec_ippo_smax.sweep10x128": {
        "env_gap": 5.960464477539063e-08, "act_gap": 4.76837158203125e-07, "act_margin": 0.0,
        "grad1_med": 7.514574336520564e-08, "delta3_med": 0.0,
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_check_numbers_are_pinned(name, monkeypatch):
    with without_chip(monkeypatch):
        result = run_small(small_cell(name))
    assert {k: c["value"] for k, c in result["checks"].items()} == PINNED[name]
