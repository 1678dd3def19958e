"""Scenario-sweep evaluation launcher — the measurement half of Block 2.

Runs any set of registered systems across any set of registered envs with
the fused greedy evaluator, and writes the ``BENCH_eval.json`` artifact:
every (system, env) cell of the support matrix, with per-cell returns over
seeds x episodes, robust aggregates (IQM + stratified-bootstrap 95% CI)
and eval steps/sec for runnable cells, and the spec-driven incompatibility
reason for the rest.

  # the full system x env compatibility matrix
  PYTHONPATH=src python -m repro.launch.eval_marl

  # a focused slice, with training before eval
  PYTHONPATH=src python -m repro.launch.eval_marl --systems qmix ippo \
      --envs smax_lite --train-iterations 2000 --seeds 0 1 2
"""
from __future__ import annotations

import argparse
import time

from repro.envs import REGISTRY as ENVS
from repro.eval.sweep import run_sweep
from repro.launch.compile_cache import use_compilation_cache
from repro.obs import ConsoleSink
from repro.systems.registry import REGISTRY as SYSTEMS


def main():
    use_compilation_cache()
    p = argparse.ArgumentParser()
    p.add_argument(
        "--systems", nargs="+", choices=sorted(SYSTEMS) + ["all"],
        default=["all"],
        help="registered systems to sweep, or 'all' for the full registry",
    )
    p.add_argument(
        "--envs", nargs="+", choices=sorted(ENVS) + ["all"], default=["all"],
        help="registered envs to sweep, or 'all' for the full registry",
    )
    p.add_argument("--seeds", type=int, nargs="+", default=[0, 1, 2])
    p.add_argument("--eval-episodes", type=int, default=32)
    p.add_argument("--num-envs", type=int, default=16, help="parallel eval envs")
    p.add_argument(
        "--train-iterations", type=int, default=0,
        help="anakin training iterations per seed before eval (0 = eval "
        "freshly-initialised params; useful for throughput/pipeline checks)",
    )
    p.add_argument("--out", default="BENCH_eval.json")
    args = p.parse_args()

    system_names = sorted(SYSTEMS) if "all" in args.systems else args.systems
    env_names = sorted(ENVS) if "all" in args.envs else args.envs
    # all human-facing output (per-cell lines inside run_sweep and the
    # closing summary here) flows through the one ConsoleSink path
    console = ConsoleSink()
    t0 = time.perf_counter()
    run_sweep(
        system_names=system_names,
        env_names=env_names,
        seeds=args.seeds,
        num_episodes=args.eval_episodes,
        num_envs=args.num_envs,
        train_iterations=args.train_iterations,
        out_path=args.out,
    )
    console.line(
        f"swept {len(system_names)} system(s) x {len(env_names)} env(s) in "
        f"{time.perf_counter() - t0:.1f}s"
    )


if __name__ == "__main__":
    main()
