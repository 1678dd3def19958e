"""Helpers for the benchmark's CPU tests: small cells and a run without the chip look."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

SMALL_TRAIN = dict(num_seeds=3, envs_per_seed=4, chunk_iterations=16, checked_lanes=2,
                   trace_chunks=2)
# every finished request is checked at this size, so the one slot that an
# `altered_action` fault touches is always in the sample
SMALL_SERVE = dict(slots=8, rate_per_s=60.0, checked_requests=1000, trace_seconds=1,
                   drain_seconds=3)


def small_cell(name):
    """The cell ``name`` at a size a CPU test holds: every width kept, fewer envs and steps."""
    cell = harness.load_cell(name)
    traffic, config = dict(cell.traffic), json.loads(json.dumps(cell.config))
    if traffic["runner"] == "anakin_seeds":
        traffic.update(SMALL_TRAIN)
        config["system_overrides"]["rollout_len"] = traffic["chunk_iterations"]
    else:
        traffic.update(SMALL_SERVE)
    return dataclasses.replace(cell, traffic=traffic, config=config)


@contextlib.contextmanager
def without_chip(monkeypatch):
    """Skip the harness's look for a chip."""
    import jax

    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peaks", lambda kind: peaks)
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda devices: 0)
    yield


def run_small(cell, seed=2**33 + 5, seconds=1.0, trace=0):
    """``run.run_cell`` on ``cell``: the whole run after the chip look."""
    import run

    args = run.parse_args(["--workload", cell.name, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
    return run.run_cell(cell, args, harness.Clock())
