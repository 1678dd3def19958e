"""Training-throughput launcher — the speed half of the measurement backbone.

Times every runner rung (python loop / fused Anakin / shard_map) and the
serial-vs-vmapped-seed speedup for a systems x envs slice, and writes the
``BENCH_speed.json`` + ``BENCH_speed.md`` perf-trajectory artifact (schema
in docs/BENCH.md, validated by ``scripts/check_bench_schema.py``).

  # the default slice (vdn + ippo + rec_ippo on matrix_game + spread + lbf)
  PYTHONPATH=src python -m repro.launch.bench_marl

  # CI smoke scale
  PYTHONPATH=src python -m repro.launch.bench_marl --systems vdn ippo \
      --envs matrix_game --iterations 64 --num-envs 4 --num-seeds 4
"""
from __future__ import annotations

import argparse
import contextlib

from repro.bench.throughput import run_bench
from repro.envs import REGISTRY as ENVS
from repro.launch.compile_cache import use_compilation_cache
from repro.obs import ConsoleSink, profile_trace
from repro.systems.registry import REGISTRY as SYSTEMS


def main():
    use_compilation_cache()
    p = argparse.ArgumentParser()
    p.add_argument(
        "--systems", nargs="+", choices=sorted(SYSTEMS) + ["all"],
        default=["vdn", "ippo", "rec_ippo"],
        help="systems to bench (default: one replay, one on-policy and "
        "one recurrent family)",
    )
    p.add_argument(
        "--envs", nargs="+", choices=sorted(ENVS) + ["all"],
        default=["matrix_game", "spread", "lbf"],
        help="envs to bench (default: the cheapest classic pair plus one "
        "gridworld, covering the fused-recurrent rung's pinned envs)",
    )
    p.add_argument("--iterations", type=int, default=256,
                   help="fused-runner training iterations per timed call")
    p.add_argument("--num-envs", type=int, default=4,
                   help="vmapped envs per run (and per device for shard_map)")
    p.add_argument("--num-seeds", type=int, default=8,
                   help="seeds for the serial-vs-vmapped comparison")
    p.add_argument("--loop-episodes", type=int, default=3,
                   help="episodes for the python-loop baseline timing")
    p.add_argument("--out", default="BENCH_speed.json")
    p.add_argument(
        "--profile", default=None, metavar="DIR",
        help="capture a jax.profiler trace of the whole bench into DIR "
        "(see docs/OBSERVABILITY.md on reading traces)",
    )
    args = p.parse_args()

    system_names = sorted(SYSTEMS) if "all" in args.systems else args.systems
    env_names = sorted(ENVS) if "all" in args.envs else args.envs
    trace_ctx = (
        profile_trace(args.profile) if args.profile
        else contextlib.nullcontext({})
    )
    with trace_ctx as trace_info:
        run_bench(
            system_names=system_names,
            env_names=env_names,
            iterations=args.iterations,
            num_envs=args.num_envs,
            num_seeds=args.num_seeds,
            loop_episodes=args.loop_episodes,
            out_path=args.out,
        )
    if args.profile:
        ConsoleSink().write(trace_info)


if __name__ == "__main__":
    main()
