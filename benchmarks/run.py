"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows. Modules:

  speedup              JAX-rewrite 10-100x claim (python loop vs fused jit)
  switch_game          Fig 4 top — DIAL communication on the switch riddle
  value_decomposition  Fig 4 bottom — VDN vs MADQN (+QMIX) on smax-lite 3m
  architectures        Fig 6 — MAD4PG centralised vs decentralised; MPE
  distribution         Fig 6 bottom right — scaling with num_executors
  roofline             assignment §Roofline table from the dry-run JSON

Every module runs in this process, except that ``roofline`` may start
dry-run children; it goes first, before this process touches JAX, so no
child contends with it for a chip.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

MODULES = [
    "roofline",
    "speedup",
    "switch_game",
    "value_decomposition",
    "architectures",
    "distribution",
]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--fast", action="store_true", help="reduced iteration counts")
    p.add_argument("--only", choices=MODULES, default=None)
    args = p.parse_args()

    mods = [args.only] if args.only else MODULES
    if "distribution" in mods:
        # several executors need several devices: split the CPU host
        # platform into 4 (a TPU host's devices are its chips, untouched).
        # JAX fixes the count at first use, so this precedes every import.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()
    print("name,us_per_call,derived")
    failed = []
    for name in mods:
        mod = __import__(f"benchmarks.{name}", fromlist=["bench"])
        t0 = time.time()
        try:
            rows = mod.bench(fast=args.fast)
        except Exception:
            failed.append(name)
            traceback.print_exc()
            continue
        for row_name, us, derived in rows:
            print(f"{row_name},{us:.1f},{derived}")
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        sys.stdout.flush()
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
