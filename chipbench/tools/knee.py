"""Find the highest offered rate the serving cell sustains: one sweep on the chip.

    python chipbench/tools/knee.py --workload ippo_smax.serve_poisson --rates 1000 2000 3000

For each rate, in one process, it serves ``--seconds`` of open-loop
arrivals with the cell's engine and prints the backlog (requests due but
not yet admitted) at each quarter of the window, the first-decision p95
and the decisions per second.  The knee is the highest rate whose backlog
does not grow across the window (here: every request answered, and the
last quarter's backlog at most twice the first's, or 8); the last line
gives it and four fifths of it, the rate the cell's traffic file fixes.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import harness  # noqa: E402


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.use_checkout_cache()
    harness.require_chips(cell.chips)
    runner = harness.runner(cell.traffic["runner"])
    system = harness.build_system(cell.config)
    sustained = []
    for rate in args.rates:
        c = dataclasses.replace(cell, traffic=dict(cell.traffic, rate_per_s=rate))
        server = runner.Server(c, args.seed, args.seconds, system=system)
        got = server.serve(drain_seconds=30.0)
        print(json.dumps({
            "rate_per_s": rate,
            "backlog_at_quarters": got["backlog"],
            "p95_ms": 1e3 * runner.p95(got["latency_s"]),
            "decisions_per_s": got["decisions"] / args.seconds,
            "median_tick_ms": 1e3 * sorted(got["tick_seconds"])[len(got["tick_seconds"]) // 2],
            "missing": got["missing"],
        }), flush=True)
        backlog = got["backlog"]
        if got["missing"] == 0 and backlog[-1] <= max(8, 2 * backlog[0]):
            sustained.append(rate)
    if sustained:
        print(json.dumps({"knee": max(sustained), "rate_per_s": round(0.8 * max(sustained), 1)}))


if __name__ == "__main__":
    main()
