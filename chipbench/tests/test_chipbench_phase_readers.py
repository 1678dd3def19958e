"""The per-phase and set-up readers on a made-up traced window and phase map."""
import json

import pytest

from chipbench_testing import BENCH, harness

from repro.obs import profile

IPPO = json.loads((BENCH / "configs" / "ippo_smax.json").read_text())
# 2 updates x rollout 128 x 10 lanes x 128 envs
STEPS = 2 * 128 * 10 * 128
PHASE_OF = {"fusion.1": "act", "fusion.2": "act", "fusion.3": "env_step", "copy.4": "observe",
            "fusion.5": "update"}
OP_SECONDS = {"%fusion.1": 0.010, "%fusion.2": 0.030, "%fusion.3": 0.020, "%copy.4": 0.001,
              "%fusion.5": 0.050, "%while.6": 0.002}
STAGES = {"trace_s": 6.5, "lower_s": 2.25, "compile_s": 11.0, "compiles": 9, "cache_hits": 9,
          "cache_misses": 0}


def _ctx(**kw):
    ctx = dict(config=IPPO, chips=1, op_seconds=OP_SECONDS, lanes=10, updates_traced=2,
               envs_per_seed=128)
    ctx.update(kw)
    return ctx


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(profile, "phase_map", lambda: dict(PHASE_OF))
    monkeypatch.setattr(profile, "last_trace", lambda: {"trace_dir": "t",
                                                        "stages_at_start": dict(STAGES)})


@pytest.mark.parametrize("phase, seconds", [("act", 0.040), ("env_step", 0.020),
                                            ("observe", 0.001), ("update", 0.050)])
def test_phase_readers_sum_their_ops_per_env_step(recorded, phase, seconds):
    reader = harness.reader(f"{phase}_ns_per_step.train")
    assert reader.read(_ctx()) == pytest.approx(1e9 * seconds / STEPS)
    # op_seconds are averaged over the chips: the time per env-step counts every chip
    assert reader.read(_ctx(chips=4)) == pytest.approx(4e9 * seconds / STEPS)


@pytest.mark.parametrize("stage", ["trace", "lower", "compile"])
def test_setup_readers_give_the_snapshot_at_the_window_start(recorded, stage):
    assert harness.reader(f"setup_{stage}_s").read(_ctx()) == STAGES[f"{stage}_s"]


def test_readers_are_absent_without_the_program_records(monkeypatch):
    monkeypatch.setattr(profile, "phase_map", lambda: {})
    monkeypatch.setattr(profile, "last_trace", lambda: None)
    assert harness.reader("act_ns_per_step.train").read(_ctx()) is None
    assert harness.reader("setup_trace_s").read(_ctx()) is None
    # a program from before the records has neither function
    monkeypatch.delattr(profile, "phase_map")
    monkeypatch.delattr(profile, "last_trace")
    assert harness.reader("update_ns_per_step.train").read(_ctx()) is None
    assert harness.reader("setup_compile_s").read(_ctx()) is None
