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


def assert_cell_loads(harness, name):
    """The rules every cell of ``harness``'s tree keeps: its files load by name and agree.

    A training cell's limits are numbers its configuration's reference
    module gives; a serving cell's are the serving check's.
    """
    cell = harness.load_cell(name)
    entry = next(w for w in harness.load_benchmark()["workloads"] if w["name"] == name)
    assert (harness.BENCH_DIR / "traffic" / f"{entry['traffic']}.json").exists()
    assert harness.runner(cell.traffic["runner"]).run
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert harness.reader(m["name"]).read
    assert cell.limits and all(v > 0 for v in cell.limits.values())
    if cell.traffic["runner"] == "anakin_seeds":
        known = harness.reference(cell.config).NUMBERS
    else:
        from reference import serve_check

        known = serve_check.NUMBERS
    assert set(cell.limits) <= set(known)


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
