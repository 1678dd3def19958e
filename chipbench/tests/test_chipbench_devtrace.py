"""The trace reduction: busy and idle time, self times of nested ops, kernel time, breakdown."""
import collections

import pytest

from chipbench_testing import BENCH

import devtrace

Event = collections.namedtuple("Event", "name start_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")
Profile = collections.namedtuple("Profile", "planes")

# 8 ms of a trace of the rec_ippo sweep on one TPU v5e, cut around the first
# scan-kernel call (the host's window span cut to match): nested while and
# conditional ops, the kernel's batched calls and host spans, under 1 MB
RECORDED = BENCH / "traces" / "rec_ippo_8ms.xplane.pb"


def _profile():
    ops = [
        Event("%while.1 = (...) while(...)", 100, 500),   # spans the next two
        Event("%fusion.2 = f32[8] fusion(...)", 100, 200),
        Event("%custom-call.3 = f32[8] custom-call(...)", 300, 250),
        Event("%fusion.4 = f32[8] fusion(...)", 800, 100),
    ]
    host = [
        Event(devtrace.WINDOW, 50, 1000),
        Event("$runner.py:1 wait", 600, 200),
    ]
    return Profile([
        Plane("/device:TPU:0", [Line("XLA Ops", ops), Line("Steps", [])]),
        Plane("/host:CPU", [Line("python", host)]),
    ])


def test_merge_clips_and_joins():
    assert devtrace.merge([(0, 5), (3, 8), (10, 20)], 2, 15) == [(2, 8), (10, 15)]


def test_self_time_of_nested_ops():
    ops = [("w", 0, 10), ("a", 0, 4), ("b", 5, 9)]
    got = devtrace.self_times(ops, 0, 10)
    assert got == {"w": 2, "a": 4, "b": 4}


def test_reduce_synthetic_profile():
    r = devtrace.reduce_profile(_profile(), 1)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 600) and [800, 900) inside the window [50, 1050)
    assert r["busy_s"] == pytest.approx(600e-9)
    assert r["op_seconds"]["%while.1"] == pytest.approx(50e-9)
    assert r["op_seconds"]["%custom-call.3"] == pytest.approx(250e-9)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["$runner.py:1 wait", pytest.approx(200e-9)]
    assert sum(g[1] for g in gaps) == pytest.approx(400e-9)


def test_reduce_needs_the_window_span():
    bare = Profile([Plane("/device:TPU:0", [Line("XLA Ops", [Event("%f", 0, 1)])])])
    with pytest.raises(ValueError):
        devtrace.reduce_profile(bare, 1)


def test_recorded_chip_trace():
    assert RECORDED.stat().st_size < 1_000_000
    r = devtrace.reduce(str(RECORDED.parent), 1)
    assert 0 < r["busy_s"] <= r["window_s"]
    # ops on one core do not overlap, so self times add up to the busy time
    assert sum(r["op_seconds"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    kernel = sum(v for k, v in r["op_seconds"].items() if "linear_recurrent_scan" in k)
    assert 0 < kernel < r["busy_s"]
    assert len(r["breakdown"]["device_ops"]) == 10
    assert r["breakdown"]["device_ops"][0][0].startswith("%vmap_jit_linear_recurrent_scan")
    assert len(r["breakdown"]["idle_gaps"]) <= 10
