"""Run one benchmark cell on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json`` (see ``chipbench/harness.py``).  With ``--trace 0`` the
result carries the cell's end-to-end metrics, taken with the profiler off;
with ``--trace 1`` a traced window replaces the timed one and the result
carries the cell's per-layer metrics, ``busy_s``/``window_s`` and a
breakdown.  Either way the check against the plain reference runs after
the window and decides ``correct``.  On any backend but a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> None:
    clock = harness.Clock()
    args = parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.use_checkout_cache()
    harness.emit(run_cell(cell, args, clock))


def run_cell(cell, args, clock):
    """Run ``cell`` as ``args`` say and return the result line's object."""
    devices = harness.require_chips(cell.chips)
    kind = devices[0].device_kind
    peaks = harness.peaks(kind)

    out = harness.runner(cell.traffic["runner"]).run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, clock=clock, peaks=peaks,
    )
    print(f"run: {json.dumps(out.get('timing', {}))} total_s={clock.now():.3f}", file=sys.stderr)

    metrics = {}
    if args.trace:
        ctx = dict(out["trace_ctx"], config=cell.config, traffic=cell.traffic, peaks=peaks)
        for m in cell.per_layer:
            value = harness.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}

    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": out["memory_peak_bytes"],
    }
    result = {
        "correct": out["correct"],
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        device["busy_s"] = out["trace_ctx"]["busy_s"]
        device["window_s"] = out["trace_ctx"]["window_s"]
        result["breakdown"] = out["trace_ctx"]["breakdown"]
    result["checks"] = out["checks"]
    return result


if __name__ == "__main__":
    main()
