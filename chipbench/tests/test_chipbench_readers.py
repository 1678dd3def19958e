"""The per-layer metric readers on a made-up traced window."""
import json

import pytest

from chipbench_testing import BENCH, harness

import counts

PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
REC = json.loads((BENCH / "configs" / "rec_ippo_smax.json").read_text())


def _ctx(**kw):
    ctx = dict(config=REC, peaks=PEAKS, busy_s=0.9, window_s=1.0, steps_per_s=1e6, chips=1,
               op_seconds={}, lanes=10, updates_traced=2, envs_per_seed=128,
               tick_seconds=[0.002, 0.004, 0.003], queue_seconds=[0.001, 0.005])
    ctx.update(kw)
    return ctx


def test_idle_share_and_mfu():
    ctx = _ctx()
    assert harness.reader("device_idle_share.train").read(ctx) == pytest.approx(10.0)
    assert harness.reader("device_idle_share.serve").read(ctx) == pytest.approx(10.0)
    want = 100 * counts.train_flops_per_env_step(REC) * 1e6 / PEAKS["bf16_flops_per_s"]
    assert harness.reader("mfu.train").read(ctx) == pytest.approx(want)


def test_roofline_is_absent_without_the_kernel_and_counts_its_ops():
    reader = harness.reader("recurrent_scan_roofline.train")
    assert reader.read(_ctx(op_seconds={"%fusion.1": 1.0})) is None
    ops = {"%jvp_jit_linear_recurrent_scan__.3": 0.05,
           "%transpose_jvp_jit_linear_recurrent_scan___.4": 0.05, "%fusion.1": 1.0}
    least = counts.scan_bytes_per_update(REC, 128) * 10 * 2 / PEAKS["hbm_bytes_per_s"]
    assert reader.read(_ctx(op_seconds=ops)) == pytest.approx(100 * least / 0.1)


def test_serving_medians():
    ctx = _ctx()
    assert harness.reader("serve_tick_ms").read(ctx) == pytest.approx(3.0)
    assert harness.reader("serve_queue_ms").read(ctx) == pytest.approx(3.0)
    assert harness.reader("serve_tick_ms").read(_ctx(tick_seconds=[])) is None
