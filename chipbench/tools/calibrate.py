"""Read the check's numbers for setting limits: sound runs, the control and planted faults.

    python chipbench/tools/calibrate.py --workload <cell> --seeds 1 2 ... \
        --control-seeds 1 2 3 --faults half_batch altered_action --fault-seeds 1 2 3

In one process, at the cell's own size: for each seed the cell's set-up
and check as a run makes them (the program's sound readings); the control
(the reference in bfloat16 in the program's place) on the control seeds;
and the program with each planted fault (``chipbench/faults.py``) on the
fault seeds.  Serving cells serve ``--seconds`` of their traffic for each
reading.  One JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import harness  # noqa: E402


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--faults", nargs="*", default=[])
    p.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--leaves", action="store_true", help="print per-leaf gaps (training)")
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.use_checkout_cache()
    harness.require_chips(cell.chips)
    import faults

    runner = harness.runner(cell.traffic["runner"])
    system = harness.build_system(cell.config)
    training = cell.traffic["runner"] == "anakin_seeds"
    table = faults.TRAIN if training else faults.SERVE

    def reading(kind, seed, sys_, control=False):
        t = time.perf_counter()
        if training:
            make = getattr(reading, "programs", {})
            obj = runner.Sweep(cell, seed, system=sys_, program=make.get(kind))
            make[kind] = obj.program
            reading.programs = make
            obj.setup()
            obj.free()
        else:
            obj = runner.Server(cell, seed, args.seconds, system=sys_)
            got = obj.serve(cell.traffic["drain_seconds"])
        detail = [] if args.leaves and training else None
        nums = obj.check(control=control, detail=detail) if training else obj.check(control=control)
        row = {"kind": kind, "seed": seed, **nums, "seconds": time.perf_counter() - t}
        if detail:
            row["leaves"] = detail
        if not training:
            row["missing"] = got["missing"]
        print(json.dumps(row), flush=True)

    for s in args.seeds:
        reading("program", s, system)
    for s in args.control_seeds:
        reading("control", s, system, control=True)
    for name in args.faults:
        broken = table[name](system)
        for s in args.fault_seeds:
            reading(name, s, broken)


if __name__ == "__main__":
    main()
